"""Closed loop, one client: ad-hoc similarity queries, one at a time.

The paper's section 5.4 rule: a query picks a row and asks for the rows
held by at least T of the columns that hold it.  So that every seed does
the same work, the traffic file fixes the sequence of (member count, T)
pairs, and ``--seed`` picks, for each pair, a fresh row held by exactly
that many columns; the sequence repeats, with new rows, until the window
closes.  Set-up serves one query of each distinct pair, on rows of its
own, so every program the window runs is compiled before it, as in a
server that has been up for a while; no query of the window has been
asked before, so the result cache answers none.
"""
from __future__ import annotations

import time
from collections import Counter, defaultdict

import jax.numpy as jnp
import numpy as np

from yardstick.harness import log
from yardstick.serve import ClosedLoop, plans


def _draw(cell, pairs) -> list:
    """One threshold query per (member count, T) pair, each over the
    columns holding a fresh row of the seed's candidate rows."""
    tr = cell.traffic
    rng = np.random.default_rng([cell.seed, 54])
    rows = rng.integers(0, cell.r, int(tr["candidate_rows"]))
    words = np.asarray(cell.cols[:, jnp.asarray(rows // 32)])
    held = ((words >> (rows % 32).astype(np.uint32)) & 1).astype(bool)
    counts = held.sum(axis=0)
    unused = defaultdict(lambda: iter(()))  # member count -> candidate rows
    for n in np.unique(counts):
        unused[int(n)] = iter(np.flatnonzero(counts == n))
    out = []
    for n, t in pairs:
        j = next(unused[n], None)
        if j is None:
            raise RuntimeError(f"too few sampled rows held by exactly {n} columns")
        out.append(("threshold", tuple(int(c) for c in np.flatnonzero(held[:, j])),
                    int(t)))
    return out


def _pairs(cell) -> tuple:
    """(warm-up pairs: each distinct pair once; the window's pairs)."""
    sizes = [tuple(p) for p in cell.traffic["sizes"]]
    stream = [sizes[i % len(sizes)] for i in range(int(cell.traffic["stream_length"]))]
    return sorted(set(sizes)), stream


def queries(cell) -> list:
    """The window's query stream of this seed, in order."""
    warm, stream = _pairs(cell)
    return _draw(cell, warm + stream)[len(warm):]


def prepare(cell) -> dict:
    warm, stream = _pairs(cell)
    drawn = _draw(cell, warm + stream)
    client = ClosedLoop(cell.start_server(), cell.names, cell.annotate)
    for desc in drawn[:len(warm)]:
        client.run_batch([desc])
    if client.answered < client.attempted:
        raise RuntimeError("a warm-up query failed")
    client.reset()
    got = plans(cell.idx, drawn[:len(warm)])
    log(f"plans of the warm-up queries: {dict(Counter(got))}")
    return {"client": client, "stream": drawn[len(warm):]}


def run(state: dict, seconds: float, sample) -> dict:
    client = state["client"]
    batches = []
    t_start = time.perf_counter()
    deadline = t_start + seconds
    t_end = t_start
    for desc in state["stream"]:
        if time.perf_counter() >= deadline:
            break
        results, t_end = client.run_batch([desc])
        batches.append((desc,))
        sample.offer(((desc,), results))
    else:
        raise RuntimeError(
            f"the stream of {len(state['stream'])} queries ran out before the "
            f"window closed: raise stream_length in the traffic file")
    return {"t_start": t_start, "t_end": t_end, "batches": batches,
            "requests": client.requests, "attempted": client.attempted,
            "answered": client.answered}
