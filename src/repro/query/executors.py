"""Executable backends for threshold plans, behind ONE dispatch point.

Every algorithm name the planner can emit resolves here (the seed repo's
planner produced ``wide_or`` / ``rbmrg_block`` / ``dsk`` names that
``threshold()`` rejected -- now each is a runnable executor):

  * device circuit family  -- scancount, scancount_streaming, looped,
    csvckt, ssum, treeadd, srtckt, sopckt (straight-line XLA bitwise code)
  * fused                  -- the Pallas kernel (interpret mode off-TPU);
    a bare threshold's kernel reads its members in place from the
    store's plane view (``TileStore.planes``), with no gather
  * tiled_fused            -- the storage engine's tile-skipping executor:
    clean tiles resolve as constants before launch, only dirty tiles are
    gathered into the fused kernel (repro.storage.run_tiled_circuit)
  * wide_or / wide_and     -- the T=1 / T=N degenerate reductions
  * rbmrg_block            -- tile-level clean/dirty pruning, bare
    thresholds only (repro.storage.tiles; tiled_fused generalises it)
  * dsk                    -- DivideSkip over host position lists, for the
    paper's sparse, T~N regime where pruning beats bit-parallel work

Backends are *shard-local* functions: they see one :class:`ShardContext`
(the tile store, dense view, compiled circuit and bare-threshold shape of
one row-range of the index) and never touch device placement themselves.
:func:`run_plan` is the single entrypoint that dispatches a plan against a
context -- ``BitmapIndex`` builds one context for its whole row space, the
sharded engine (``repro.dist.query``) builds one per device shard and can
hand each shard a different plan.
"""
from __future__ import annotations

import dataclasses
import threading
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bitmaps import WORD_DTYPE, from_positions, to_positions_np
from repro.core.planner import CIRCUIT_BACKENDS
from repro.query.execinfo import make_exec_info

__all__ = [
    "THRESHOLD_BACKENDS",
    "ShardContext",
    "bare_members_info",
    "run_plan",
    "run_threshold_backend",
]

_DEVICE_ALGOS = (
    "scancount", "scancount_streaming", "looped", "csvckt",
    "ssum", "treeadd", "srtckt", "sopckt",
)

THRESHOLD_BACKENDS = _DEVICE_ALGOS + (
    "fused", "tiled_fused", "wide_or", "wide_and", "rbmrg_block", "dsk",
)


#: process-wide counts of bare-threshold executions by how the members
#: were read: in place from the store's plane view by the fused kernel
#: (``planes``), or gathered as rows of the dense view (``rows``)
_BARE_MEMBERS = {"planes": 0, "rows": 0}
_BARE_MEMBERS_LOCK = threading.Lock()


def _count_bare(members: str) -> None:
    with _BARE_MEMBERS_LOCK:
        _BARE_MEMBERS[members] += 1


def bare_members_info() -> dict:
    """Bare-threshold executions by member read (surfaced by
    ``QueryServer.info()``)."""
    with _BARE_MEMBERS_LOCK:
        return dict(_BARE_MEMBERS)


@partial(jax.jit, static_argnames=("t", "algorithm"))
def _device_threshold(bitmaps: jax.Array, t: int, algorithm: str) -> jax.Array:
    from repro.core.threshold import (
        _circuit_threshold,
        _csvckt,
        _looped,
        _scancount,
        _scancount_streaming,
    )

    if algorithm == "scancount":
        return _scancount(bitmaps, t)
    if algorithm == "scancount_streaming":
        return _scancount_streaming(bitmaps, t)
    if algorithm == "looped":
        return _looped(bitmaps, t)
    if algorithm == "csvckt":
        return _csvckt(bitmaps, t)
    return _circuit_threshold(bitmaps, t, algorithm)


@jax.jit
def _wide_or(bitmaps: jax.Array) -> jax.Array:
    return jnp.bitwise_or.reduce(bitmaps, axis=0)


@jax.jit
def _wide_and(bitmaps: jax.Array) -> jax.Array:
    # jnp.bitwise_and.reduce rejects uint32 (its -1 init overflows); De Morgan
    return jnp.bitwise_not(jnp.bitwise_or.reduce(jnp.bitwise_not(bitmaps), axis=0))


def _dsk_threshold(bitmaps: jax.Array, t: int) -> jax.Array:
    """Host DivideSkip over per-bitmap sorted position lists."""
    from repro.core.listalgos import dsk

    arr = np.asarray(jax.device_get(bitmaps), dtype=np.uint32)
    r = arr.shape[1] * 32
    lists = [to_positions_np(row) for row in arr]
    return from_positions(dsk(lists, t, r), r)


@dataclasses.dataclass
class ShardContext:
    """Everything a shard-local backend needs to execute one plan.

    A *shard* is a row-range of the universe: the whole index on a single
    device, or one device's tile range under ``repro.dist.query``.  Data
    accessors are thunks so a backend only pays for the representation it
    reads -- ``tiled_fused`` builds the tile store, dense backends pull the
    packed view, and neither forces the other.
    """

    n: int  # columns in the shard (same for every shard of an index)
    dense: Callable  # () -> uint32[n, local_words] packed dense view
    store: Callable | None = None  # () -> TileStore (tile-classified shard)
    circuit: Callable | None = None  # () -> compiled Circuit (shared, cached)
    bare: tuple | None = None  # (member slots | None, T) for bare thresholds
    column: int | None = None  # slot for 'column' plans
    block_words: int | None = None
    #: tiled case-3 engine override: "scan" (single-dispatch device engine)
    #: / "merge" (host event-merge oracle) / None (auto per store)
    tiled_engine: str | None = None

    def member_rows(self) -> jax.Array:
        """Dense rows of the bare-threshold member subset (gathered)."""
        rows = self.dense()
        slots = self.bare[0]
        if slots is not None:
            rows = rows[jnp.asarray(list(slots))]
        return rows


def _dense_exec_info(backend: str, engine: str, n_rows: int, out,
                     launches: int = 1, members: str = "") -> dict:
    """ExecInfo for a backend that reads every member row densely.

    ``words_touched`` is the roofline traffic term: N input rows read plus
    each output row written, all at the shard's word width.  Members read
    in place from the plane view (``members="planes"``) gather nothing.
    """
    k = 1 if out.ndim == 1 else out.shape[0]
    nw = int(out.shape[-1])
    total = n_rows * nw + k * nw
    return make_exec_info(
        backend,
        engine=engine,
        kernel="pallas" if backend == "fused" else (
            "xla" if engine == "dense" else ""
        ),
        members=members,
        n_outputs=k,
        total_words=total,
        words_touched=total,
        dirty_words_gathered=0 if members == "planes" else n_rows * nw,
        words_by_kind={"dense": n_rows * nw},
        launches=launches,
        work_fraction=1.0,
    )


def run_plan(ctx: ShardContext, plan):
    """THE executor entrypoint: run one plan against one shard's data.

    ``plan`` is a ``core.planner.Plan`` or a backend name.  Returns
    ``(packed result, info)`` -- ``info`` is an ExecInfo
    (:mod:`repro.query.execinfo`): the tiled executor's case-split
    accounting when it ran, a dense-traffic accounting for every other
    backend.  Every backend resolves through here; callers own device
    placement, backends own compute.
    """
    alg = getattr(plan, "algorithm", plan)
    if alg == "column":
        if ctx.column is None:
            raise ValueError("'column' plan without a column slot in the context")
        out = ctx.dense()[ctx.column]
        nw = int(out.shape[-1])
        return out, make_exec_info(
            "column", engine="view", total_words=nw, words_touched=nw,
            words_by_kind={"dense": nw}, launches=0, work_fraction=1.0,
        )
    if alg == "tiled_fused":
        if ctx.store is None or ctx.circuit is None:
            raise ValueError("'tiled_fused' needs a tile store and a compiled circuit")
        from repro.storage import run_tiled_circuit

        out, info = run_tiled_circuit(
            ctx.store(), ctx.circuit(), block_words=ctx.block_words,
            engine=ctx.tiled_engine,
        )
        return out, info
    if alg == "fused" and ctx.bare is not None:
        return _fused_bare(ctx)
    if alg in THRESHOLD_BACKENDS and ctx.bare is not None:
        rows = ctx.member_rows()
        out = run_threshold_backend(
            rows, ctx.bare[1], alg, block_words=ctx.block_words
        )
        engine = "host" if alg == "dsk" else "dense"
        _count_bare("rows")
        return out, _dense_exec_info(
            alg, engine, int(rows.shape[0]), out, members="rows"
        )
    if alg in CIRCUIT_BACKENDS:
        from repro.kernels.threshold_ssum import INTERPRET, run_circuit_cached

        if ctx.circuit is None:
            raise ValueError(f"backend {alg!r} needs a compiled circuit in the context")
        rows = ctx.dense()
        out = run_circuit_cached(
            rows,
            ctx.circuit(),
            block_words=ctx.block_words,
            interpret=INTERPRET,
            pallas=alg == "fused",
        )
        return out, _dense_exec_info(alg, "dense", int(rows.shape[0]), out)
    if alg in THRESHOLD_BACKENDS:
        raise ValueError(
            f"backend {alg!r} only executes bare Threshold queries; "
            "use 'circuit', 'fused' or 'tiled_fused' for composite expressions"
        )
    raise ValueError(f"unknown backend {alg!r}")


def _fused_bare(ctx: ShardContext):
    """A bare threshold through the fused kernel, its members read in
    place from the store's plane view: the slots go to the kernel as a
    host array, so one jitted call is the whole device work."""
    from repro.kernels.threshold_ssum import INTERPRET, threshold_pallas

    if ctx.store is None:
        raise ValueError("'fused' bare thresholds read a tile store's plane view")
    store = ctx.store()
    slots, t = ctx.bare
    slots = np.asarray(range(ctx.n) if slots is None else slots, np.int32)
    out = threshold_pallas(
        store.planes(), slots, t, n_words=store.n_words, interpret=INTERPRET
    )
    _count_bare("planes")
    return out, _dense_exec_info(
        "fused", "dense", int(slots.shape[0]), out, members="planes"
    )


def run_threshold_backend(
    bitmaps: jax.Array, t: int, backend: str, *, block_words: int | None = None
) -> jax.Array:
    """theta(T, .) over packed uint32[N, n_words] via a named backend.

    T must be a static Python int (circuits are tabulated per (N, T)).
    T <= 0 and T > N short-circuit before backend dispatch.
    """
    if not isinstance(t, int):
        raise TypeError("T must be a static Python int (circuits are tabulated per (N,T))")
    bitmaps = jnp.asarray(bitmaps, WORD_DTYPE)
    if bitmaps.ndim != 2:
        raise ValueError(f"expected uint32[N, n_words], got shape {bitmaps.shape}")
    n = bitmaps.shape[0]
    if t <= 0:
        return jnp.full_like(bitmaps[0], 0xFFFFFFFF)
    if t > n:
        return jnp.zeros_like(bitmaps[0])
    if backend == "wide_or":
        if t != 1:
            raise ValueError(f"wide_or computes theta(1, .); got T={t}")
        return _wide_or(bitmaps)
    if backend == "wide_and":
        if t != n:
            raise ValueError(f"wide_and computes theta(N, .); got T={t}, N={n}")
        return _wide_and(bitmaps)
    if backend == "rbmrg_block":
        from repro.storage import rbmrg_block_threshold

        out, _info = rbmrg_block_threshold(bitmaps, t)
        return out
    if backend == "tiled_fused":
        from repro.core.circuits import build_threshold_circuit
        from repro.storage import TileStore, run_tiled_circuit

        store = TileStore.from_packed(bitmaps)
        circ = build_threshold_circuit(n, t, "ssum")
        out, _info = run_tiled_circuit(store, circ, block_words=block_words)
        return out
    if backend == "dsk":
        return _dsk_threshold(bitmaps, t)
    if backend == "fused":
        from repro.kernels.threshold_ssum import INTERPRET, threshold_rows

        return threshold_rows(bitmaps, t, interpret=INTERPRET)
    if backend in _DEVICE_ALGOS:
        return _device_threshold(bitmaps, t, backend)
    raise ValueError(f"unknown algorithm {backend!r}; valid: {THRESHOLD_BACKENDS}")
