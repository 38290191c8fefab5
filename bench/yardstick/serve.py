"""The system under test, seen from its client: the index, the server,
and the closed loop that drives ``QueryServer.submit``.

This is the only module of the benchmark that imports ``repro``.
"""
from __future__ import annotations

import threading
import time

import jax
import numpy as np

from repro.query import And, BitmapIndex, Col, Interval, Not, Threshold
from repro.serve import QueryServer


def names_for(n: int) -> tuple:
    return tuple(f"b{i:02d}" for i in range(n))


def to_query(q, names: tuple):
    """A query description (:mod:`yardstick.reference`) as the program's
    query object."""
    op = q[0]
    if op in ("threshold", "interval"):
        over = None if q[1] is None else tuple(names[i] for i in q[1])
        if op == "threshold":
            return Threshold(q[2], over=over)
        return Interval(q[2], q[3], over=over)
    if op == "col":
        return Col(names[q[1]])
    if op == "not":
        return Not(to_query(q[1], names))
    if op == "and":
        return And(*(to_query(c, names) for c in q[1]))
    raise ValueError(f"unknown query op {op!r}")


def build_index(cols: jax.Array, r: int, index_cfg: dict) -> BitmapIndex:
    """The index as a user builds it from packed columns: the store is
    classified on the host, and its device arrays are made as the
    planner's choices need them."""
    idx = BitmapIndex(cols, names_for(cols.shape[0]), r=r,
                      tile_words=int(index_cfg["tile_words"]),
                      containers=bool(index_cfg["containers"]))
    idx.store  # noqa: B018 - classification is the build
    return idx


def plans(idx: BitmapIndex, descs) -> list:
    """The planner's backend for each description."""
    return [idx.explain(to_query(q, idx.names)).algorithm for q in descs]


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length,
    drawn with a seeded generator (Algorithm R)."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen, self.items = int(k), rng, 0, []

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
            return
        j = int(self.rng.integers(self.seen))
        if j < self.k:
            self.items[j] = item


class ClosedLoop:
    """One client that submits a batch of queries at once and waits for
    every answer before the next batch.

    A request is one batch, what the client waits for: here one ad-hoc
    query.  Each is logged as (t_submit, t_done or None,
    ok) on the ``perf_counter`` clock: submit of its first query, and
    resolution of its last future (None, and not ok, if any query failed
    or never resolved).  ``attempted`` and ``answered`` count queries.
    ``annotate(name)`` brackets the submits and the waits (a
    ``jax.profiler.TraceAnnotation``), so a trace can say what the client
    was doing in each idle gap of the device."""

    def __init__(self, server: QueryServer, names: tuple, annotate):
        self.server, self.names, self.annotate = server, names, annotate
        self.requests: list = []
        self.attempted = self.answered = 0

    def reset(self) -> None:
        self.requests.clear()
        self.attempted = self.answered = 0

    def run_batch(self, descs, queries=None, *, wait_s: float = 60.0):
        """Submit ``descs`` together and wait; returns (results or None per
        query, time the last one resolved)."""
        queries = queries or [to_query(q, self.names) for q in descs]
        futs = []
        with self.annotate("bench:submit"):
            for q in queries:
                t_sub = time.perf_counter()
                done, ev = [None], threading.Event()
                try:
                    fut = self.server.submit(q)
                except Exception:  # noqa: BLE001 - shed or refused: a failure
                    futs.append((t_sub, None, done, ev))
                    continue

                def on_done(_f, d=done, e=ev):
                    d[0] = time.perf_counter()
                    e.set()

                fut.add_done_callback(on_done)
                futs.append((t_sub, fut, done, ev))
        results = []
        with self.annotate("bench:wait"):
            for _t, fut, _done, ev in futs:
                out = None
                if fut is not None and ev.wait(wait_s):
                    try:
                        out = fut.result()
                    except Exception:  # noqa: BLE001 - the query failed
                        out = None
                results.append(out)
        ok = all(out is not None for out in results)
        t_last = max((d[0] for _s, _f, d, _e in futs if d[0] is not None),
                     default=time.perf_counter())
        self.requests.append((futs[0][0], t_last if ok else None, ok))
        self.attempted += len(results)
        self.answered += sum(out is not None for out in results)
        return results, t_last

