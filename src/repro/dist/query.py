"""Row-sharded multi-device execution engine for BitmapIndex queries.

The paper's algorithms assume one machine; Roaring's container-per-chunk
design shows the row space is the natural unit of both compression and
parallelism, and threshold / symmetric functions are computed *pointwise*
per row position -- so a row-range shard of every column is a complete,
independent sub-problem whose result is again a bitmap shard.  That is
exactly what composes: sharded results feed back as sharded columns via
``add_column`` with no gather.

  * :class:`ShardedTileStore` partitions a :class:`~repro.storage.TileStore`
    into contiguous tile ranges, one per device shard.  Slicing shares the
    classified tiles and dirty words (no reclassification); each shard
    carries its own tile classes, dirty pack, offsets table and member
    statistics.
  * :class:`ShardedBitmapIndex` compiles ONE circuit per query shape
    (shared through the process-wide compiled cache) and plans PER SHARD:
    the planner's words-touched cost model runs on each shard's local
    statistics, so a mostly-clean shard takes ``tiled_fused`` while a dense
    shard takes the circuit path -- heterogeneous backends behind one
    ``execute`` call, each dispatched through the same
    :func:`repro.query.executors.run_plan` entrypoint.
  * When every shard's plan is dense-circuit-evaluable and a mesh is
    installed, the whole query runs as one ``shard_map`` over the
    device-sharded word axis (the SPMD fast path); otherwise shards run
    host-sequenced, each on its own representation.
  * With a mesh, shard ``i`` lives on the ``i``-th device along the mesh
    axis: its dense view, container packs and results are placed there,
    and the ``shard_map`` input is assembled from those per-device arrays
    without a gather.

An 8-device host-platform CPU mesh (``XLA_FLAGS=
--xla_force_host_platform_device_count=8``) exercises the full path in CI.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bitmaps import WORD_DTYPE, packed_tail_mask
from repro.core.planner import Plan, plan_query
from repro.storage import TileStore

__all__ = [
    "ShardedTileStore",
    "ShardedBitmapIndex",
    "ShardedResult",
    "ShardedPlan",
    "shard_boundaries",
]

# Backends whose result is exactly "evaluate the compiled circuit" -- under
# the SPMD path the one shared circuit is evaluated in-place of any of them
# (bit-identical: every backend computes the same Boolean function).  The
# tile-skipping / host-list backends stay shard-local, and so do the
# scancount executors: they are chosen precisely when N is too large to
# tabulate a per-(N, T) circuit, so substituting circuit evaluation there
# would compile the very adder the plan is avoiding.
_SPMD_BACKENDS = frozenset(
    (
        "circuit", "fused", "ssum", "treeadd", "srtckt", "sopckt", "csvckt",
        "wide_or", "wide_and", "looped",
    )
)


# Jitted shard_map runners cached by circuit STRUCTURE (+ mesh/axis), like
# kernels.threshold_ssum's structural jit cache: repeated queries -- the
# serving admission loop above all -- trace and compile once per circuit
# shape, never once per call.
_SPMD_RUNNERS: dict = {}
_SPMD_RUNNERS_CAP = 256


def _gather(parts, axis: int = 0) -> jax.Array:
    """Concatenate per-shard arrays on the default device: the explicit,
    paid-for gather (placed shards live on different devices)."""
    home = jax.devices()[0]
    return jnp.concatenate([jax.device_put(p, home) for p in parts], axis=axis)


def _block_devices(mesh, axis: str) -> list:
    """The first device of each block along ``axis``: where a mesh places
    shard ``i`` (the other mesh axes hold replicas of the block)."""
    devs = np.moveaxis(mesh.devices, mesh.axis_names.index(axis), 0)
    return list(devs.reshape(devs.shape[0], -1)[:, 0])


def _spmd_runner(circuit, mesh, axis: str, n: int):
    from jax.sharding import PartitionSpec as P

    from repro.kernels.threshold_ssum import circuit_structural_key

    key = (circuit_structural_key(circuit), mesh, axis, n)
    fn = _SPMD_RUNNERS.get(key)
    if fn is None:
        if len(_SPMD_RUNNERS) >= _SPMD_RUNNERS_CAP:
            _SPMD_RUNNERS.clear()

        def local(blk):
            outs = circuit.evaluate([blk[i] for i in range(n)])
            return jnp.stack([jnp.broadcast_to(o, blk.shape[1:]) for o in outs])

        fn = jax.jit(
            jax.shard_map(
                local, mesh=mesh, in_specs=P(None, axis), out_specs=P(None, axis)
            )
        )
        _SPMD_RUNNERS[key] = fn
    return fn


def shard_boundaries(n_tiles: int, n_shards: int) -> tuple:
    """Contiguous tile ranges [(t0, t1), ...], as even as possible."""
    n_shards = max(1, min(int(n_shards), int(n_tiles)))
    base, extra = divmod(n_tiles, n_shards)
    bounds, t0 = [], 0
    for i in range(n_shards):
        t1 = t0 + base + (1 if i < extra else 0)
        bounds.append((t0, t1))
        t0 = t1
    return tuple(bounds)


class ShardedTileStore:
    """A TileStore partitioned into per-device row-range shards.

    Each shard is itself a :class:`~repro.storage.TileStore` over its tile
    range: its own classes, dirty pack, offsets table, and (lazily built)
    member statistics.  Stores stay immutable -- ``append`` / ``replace``
    return a new sharded store whose shards share the untouched columns.
    """

    def __init__(self, shards: tuple, tile_bounds: tuple, *, n_words: int,
                 r: int, mesh=None, axis: str = "data"):
        shards = tuple(shards)
        if mesh is not None and len(shards) == _axis_size(mesh, axis):
            # shard i lives on the i-th device along the axis
            shards = tuple(
                s.on_device(d) if hasattr(s, "on_device") else s
                for s, d in zip(shards, _block_devices(mesh, axis))
            )
        self.shards: tuple = shards
        self.tile_bounds = tuple(tile_bounds)
        self.n_words = int(n_words)
        self.r = int(r)
        self.mesh = mesh
        self.axis = axis
        self.tile_words = self.shards[0].tile_words
        #: word offset of each shard's first word in the global row space
        self.word_offsets = tuple(t0 * self.tile_words for t0, _ in self.tile_bounds)
        self._dense_cache = None
        self._spmd_cache: dict = {}  # (mesh, axis) -> device-sharded dense

    @classmethod
    def from_store(cls, store: TileStore, *, n_shards: int | None = None,
                   mesh=None, axis: str = "data") -> "ShardedTileStore":
        if n_shards is None:
            n_shards = _axis_size(mesh, axis) if mesh is not None else 1
        bounds = shard_boundaries(store.n_tiles, n_shards)
        shards = tuple(store.slice_tiles(t0, t1) for t0, t1 in bounds)
        return cls(shards, bounds, n_words=store.n_words, r=store.r,
                   mesh=mesh, axis=axis)

    # -- accessors ---------------------------------------------------------
    @property
    def n(self) -> int:
        return self.shards[0].n

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    def densify(self) -> jax.Array:
        """Global dense uint32[N, n_words] view (an explicit gather; cached
        -- the store is immutable)."""
        if self._dense_cache is None:
            self._dense_cache = _gather([s.densify() for s in self.shards], 1)
        return self._dense_cache

    @property
    def spmd_width(self) -> int:
        """Words per device block of :meth:`spmd_dense`: the widest shard."""
        return max(s.n_words for s in self.shards)

    def spmd_dense(self, mesh, axis: str) -> jax.Array:
        """Device-sharded dense view for the shard_map path: block ``i``
        of the word axis is shard ``i``'s dense view, zero-padded to
        :attr:`spmd_width` and assembled on its own device -- nothing is
        gathered.  Needs one shard per device along ``axis``; cached per
        mesh/axis, so columns stay resident across queries."""
        key = (mesh, axis)
        got = self._spmd_cache.get(key)
        if got is None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            if _axis_size(mesh, axis) != self.n_shards:
                raise ValueError(
                    f"{self.n_shards} shards on a {axis!r} axis of size "
                    f"{_axis_size(mesh, axis)}"
                )
            w = self.spmd_width
            blocks = []
            for s in self.shards:
                d = s.densify()
                if d.shape[1] != w:
                    d = jnp.pad(d, ((0, 0), (0, w - d.shape[1])))
                blocks.append(d)
            sharding = NamedSharding(mesh, P(None, axis))
            shape = (self.n, w * self.n_shards)
            got = jax.make_array_from_single_device_arrays(
                shape, sharding,
                [
                    jax.device_put(blocks[idx[1].start // w], dev)
                    for dev, idx in sharding.addressable_devices_indices_map(
                        shape
                    ).items()
                ],
            )
            self._spmd_cache[key] = got
        return got

    def member_stats(self, slots=None) -> tuple:
        """Per-shard planner statistics of a member subset."""
        return tuple(s.member_stats(slots) for s in self.shards)

    def with_shards(self, shards) -> "ShardedTileStore":
        """New sharded store with the shard stores swapped out -- the
        streaming engine's per-shard overlay/compaction constructor
        (``repro.stream``).  Accepts TileStore-shaped objects (e.g.
        ``OverlayStore`` read views); tile bounds are recomputed from the
        shards' own sizes, so growth in the LAST shard (``append_rows``
        extending the universe) is reflected without resharding.  Interior
        shards hold only whole tiles, so their boundaries cannot move."""
        shards = tuple(shards)
        if len(shards) != self.n_shards:
            raise ValueError(f"{len(shards)} shards for {self.n_shards}")
        bounds, t0 = [], 0
        for s in shards:
            bounds.append((t0, t0 + s.n_tiles))
            t0 = bounds[-1][1]
        off_words = bounds[-1][0] * self.tile_words
        return ShardedTileStore(
            shards, bounds,
            n_words=off_words + shards[-1].n_words,
            r=off_words * 32 + shards[-1].r,
            mesh=self.mesh, axis=self.axis,
        )

    # -- immutable updates -------------------------------------------------
    def split(self, packed) -> tuple:
        """Split a global packed row uint32[n_words] into per-shard parts."""
        row = jnp.asarray(packed, WORD_DTYPE)
        if row.shape != (self.n_words,):
            raise ValueError(f"expected shape ({self.n_words},), got {row.shape}")
        parts, off = [], list(self.word_offsets) + [self.n_words]
        for i in range(self.n_shards):
            parts.append(row[off[i] : off[i + 1]])
        return tuple(parts)

    def _as_parts(self, packed_or_parts) -> tuple:
        if isinstance(packed_or_parts, (tuple, list)):
            parts = tuple(packed_or_parts)
            if len(parts) != self.n_shards:
                raise ValueError(
                    f"{len(parts)} parts for {self.n_shards} shards"
                )
            return parts
        return self.split(packed_or_parts)

    def append(self, packed_or_parts) -> "ShardedTileStore":
        """New sharded store with one more column.  Accepts per-shard parts
        (a query result's shards -- NO gather) or a global packed row."""
        parts = self._as_parts(packed_or_parts)
        return ShardedTileStore(
            tuple(s.append(p) for s, p in zip(self.shards, parts)),
            self.tile_bounds, n_words=self.n_words, r=self.r,
            mesh=self.mesh, axis=self.axis,
        )

    def replace(self, i: int, packed_or_parts) -> "ShardedTileStore":
        """New sharded store with column ``i`` swapped (shard-wise)."""
        parts = self._as_parts(packed_or_parts)
        return ShardedTileStore(
            tuple(s.replace(i, p) for s, p in zip(self.shards, parts)),
            self.tile_bounds, n_words=self.n_words, r=self.r,
            mesh=self.mesh, axis=self.axis,
        )


@dataclasses.dataclass(frozen=True)
class ShardedResult:
    """A query result that never left its shards: one packed bitmap piece
    per shard (already tail-masked to the shard's universe slice).  Feed it
    straight back via ``ShardedBitmapIndex.add_column`` -- composing results
    is the whole point of keeping them bitmaps (1402.4466), and sharding
    preserves it because symmetric functions are pointwise per row."""

    shards: tuple  # uint32[local_words] per shard
    word_offsets: tuple
    n_words: int
    r: int

    def gather(self) -> jax.Array:
        """Materialise the global packed bitmap (the one explicit gather)."""
        return _gather(self.shards)


@dataclasses.dataclass(frozen=True)
class ShardedPlan:
    """Per-shard plans for one query (the heterogeneous-backend contract)."""

    plans: tuple  # core.planner.Plan per shard

    @property
    def backends(self) -> tuple:
        return tuple(p.algorithm for p in self.plans)

    @property
    def distinct(self) -> tuple:
        return tuple(sorted(set(self.backends)))

    @property
    def cost(self) -> float:
        return float(sum(p.cost or 0.0 for p in self.plans))


def _axis_size(mesh, axis: str) -> int:
    from repro.launch.mesh import mesh_axis_sizes

    sizes = mesh_axis_sizes(mesh)
    if axis not in sizes:
        raise ValueError(f"mesh has no axis {axis!r}; axes: {tuple(sizes)}")
    return int(sizes[axis])


class ShardedBitmapIndex:
    """A BitmapIndex whose row space lives in per-device shards.

    ``execute`` compiles ONE circuit (process-wide cache, shared with the
    unsharded engine) and runs a per-shard plan: every shard's backend is a
    shard-local function dispatched through ``run_plan``; with a mesh and
    all-dense plans the query instead runs as a single ``shard_map``.
    Results are :class:`ShardedResult`s and feed back via
    :meth:`add_column` without a gather.  Like ``BitmapIndex``, instances
    are immutable -- ``add_column`` / ``replace_column`` return a NEW index
    and stale references keep executing against their own schema.
    """

    def __init__(self, store: ShardedTileStore, names: tuple):
        self.store = store
        self._names = tuple(names)
        if len(self._names) != store.n:
            raise ValueError(f"{len(self._names)} names for {store.n} columns")
        self._slot = {name: i for i, name in enumerate(self._names)}
        self.r = store.r
        self.n_words = store.n_words
        #: merged info of the last execution (per-shard backends + accounting)
        self.last_info: dict | None = None

    @classmethod
    def from_index(cls, index, *, mesh=None, axis: str = "data",
                   n_shards: int | None = None) -> "ShardedBitmapIndex":
        store = ShardedTileStore.from_store(
            index.store, n_shards=n_shards, mesh=mesh, axis=axis
        )
        return cls(store, index.names)

    # -- accessors ---------------------------------------------------------
    @property
    def names(self) -> tuple:
        return self._names

    @property
    def n(self) -> int:
        return self.store.n

    @property
    def n_shards(self) -> int:
        return self.store.n_shards

    @property
    def mesh(self):
        return self.store.mesh

    def __contains__(self, name: str) -> bool:
        return name in self._slot

    def __getitem__(self, name: str):
        from repro.query.expr import Col

        if name not in self._slot:
            raise KeyError(f"unknown column {name!r}")
        return Col(name)

    def column(self, name: str) -> jax.Array:
        """Gathered dense view of one column (for host-side comparisons)."""
        if name not in self._slot:
            raise KeyError(f"unknown column {name!r}")
        i = self._slot[name]
        return _gather([s.densify()[i] for s in self.store.shards])

    # -- immutable updates -------------------------------------------------
    def add_column(self, name: str, result) -> "ShardedBitmapIndex":
        """New index with a (virtual) column appended shard-wise.  ``result``
        is a :class:`ShardedResult`, per-shard parts, or a global packed row;
        sharded results are consumed with NO gather."""
        if name in self._slot:
            raise ValueError(f"column {name!r} already exists")
        parts = result.shards if isinstance(result, ShardedResult) else result
        return ShardedBitmapIndex(
            self.store.append(parts), self._names + (name,)
        )

    def replace_column(self, name: str, result) -> "ShardedBitmapIndex":
        """New index with one column's shards swapped; untouched columns
        share storage, stale references keep working."""
        if name not in self._slot:
            raise KeyError(f"unknown column {name!r}")
        parts = result.shards if isinstance(result, ShardedResult) else result
        return ShardedBitmapIndex(
            self.store.replace(self._slot[name], parts), self._names
        )

    # -- planning ----------------------------------------------------------
    def _member_slots(self, q):
        from repro.query.index import member_slots

        return member_slots(q, self._slot)

    def _bare_slots(self, q):
        from repro.query.index import bare_slots

        return bare_slots(q, self._slot)

    def plan(self, query) -> ShardedPlan:
        """Per-shard plans from each shard's LOCAL member statistics -- a
        mostly-clean shard gets ``tiled_fused`` while a dense shard gets the
        circuit path, behind the same query call."""
        from repro.query.expr import as_query
        from repro.query.index import _fused_available

        q = as_query(query)
        slots = self._member_slots(q)
        fused = _fused_available()
        return ShardedPlan(
            tuple(
                plan_query(q, self.n, stats=shard.member_stats(slots),
                           fused_available=fused)
                for shard in self.store.shards
            )
        )

    # -- execution ---------------------------------------------------------
    def execute(self, query, *, backend: str | None = None,
                block_words: int | None = None) -> ShardedResult:
        """Evaluate one expression across every shard.  Returns a
        :class:`ShardedResult` (per-shard packed bitmaps, tail-masked)."""
        from repro.query.expr import as_query

        q = as_query(query)
        outs = self._execute_circuit((q,), [q], backend, block_words)
        return outs[0]

    def execute_many(self, queries, *, backend: str | None = None,
                     block_words: int | None = None) -> list:
        """Evaluate independent queries: ONE multi-output circuit, one
        per-shard plan, one dirty-tile gather (tiled shards) or one
        evaluation sweep (dense shards) shared by all of them."""
        from repro.query.expr import as_query

        qs = [as_query(x) for x in queries]
        return self._execute_circuit(tuple(qs), qs, backend, block_words)

    # -- internals ---------------------------------------------------------
    def _circuit_fn(self, qs: tuple):
        from repro.query.index import circuit_for

        return lambda: circuit_for(qs, self.n, self._names)

    def _execute_circuit(self, qs: tuple, qlist, backend, block_words) -> list:
        import repro.obs as _obs
        from repro.obs import trace as _trace

        with _trace.span(
            "execute_sharded", n_shards=self.n_shards, n_queries=len(qlist)
        ) as root:
            out = self._execute_circuit_inner(
                qs, qlist, backend, block_words
            )
            if _trace.enabled or _obs.REGISTRY.enabled:
                self._observe(root)
        return out

    def _observe(self, root) -> None:
        """Predicted-vs-measured accounting for the whole sharded call."""
        import repro.obs as _obs

        info = self.last_info or {}
        measured = info.get("words_touched")
        plans = getattr(self, "_last_plans", None)
        costs = [
            p.cost for p in (plans.plans if plans else ())
            if getattr(p, "cost", None) is not None
        ]
        backends = sorted(set(info.get("backends", ())))
        label = backends[0] if len(backends) == 1 else "mixed"
        root.set(
            mode=info.get("mode"),
            backends=backends,
            predicted_words=sum(costs) if costs else None,
            measured_words=measured,
        )
        if measured is not None:
            _obs.record_drift(label, sum(costs) if costs else None, measured)

    def _execute_circuit_inner(self, qs: tuple, qlist, backend, block_words) -> list:
        circ_fn = self._circuit_fn(qs)
        if backend is not None:
            plans = ShardedPlan(
                tuple(Plan(backend, "caller override") for _ in self.store.shards)
            )
        elif len(qlist) == 1:
            plans = self.plan(qlist[0])
        else:
            # multi-query: plan each shard once over all columns; any shard
            # whose stats favour skipping runs the whole batch tiled, the
            # rest evaluate the multi-output circuit (only circuit-family
            # backends can produce k outputs in one pass)
            from repro.query.index import _fused_available

            fused = _fused_available()
            shard_plans = []
            for shard in self.store.shards:
                p = plan_query(qlist[0], self.n, stats=shard.member_stats(None),
                               fused_available=fused)
                if p.algorithm != "tiled_fused":
                    p = Plan("fused" if fused else "circuit",
                             f"multi-query batch (shard plan was {p.algorithm})",
                             cost=p.cost, candidates=p.candidates)
                shard_plans.append(p)
            plans = ShardedPlan(tuple(shard_plans))
        self._last_plans = plans
        k = len(qlist)
        if (
            self.mesh is not None
            and _axis_size(self.mesh, self.store.axis) == self.n_shards
            and all(b in _SPMD_BACKENDS for b in plans.backends)
        ):
            stacked = self._run_spmd(circ_fn(), k)
            self.last_info = {
                "mode": "shard_map",
                "backends": plans.backends,
                "n_shards": self.n_shards,
            }
        else:
            stacked = self._run_per_shard(circ_fn, qlist, plans, block_words)
        results = []
        for j in range(k):
            results.append(
                ShardedResult(
                    shards=tuple(stacked[i][j] for i in range(self.n_shards)),
                    word_offsets=self.store.word_offsets,
                    n_words=self.n_words,
                    r=self.r,
                )
            )
        return results

    def _run_spmd(self, circuit, k: int) -> list:
        """One shard_map over the device-sharded word axis: every device
        evaluates the same compiled circuit on its local words (threshold /
        symmetric functions are pointwise per row position, so the split is
        exact).  Columns, the jitted runner, and the results all stay
        device-resident across calls (both caches are keyed structurally);
        shard ``i``'s result is read from the block on its own device."""
        mesh, axis = self.mesh, self.store.axis
        arr = self.store.spmd_dense(mesh, axis)
        fn = _spmd_runner(circuit, mesh, axis, self.n)
        out = fn(arr)
        w = self.store.spmd_width
        homes = _block_devices(mesh, axis)
        blocks = {
            piece.index[1].start // w: piece.data
            for piece in out.addressable_shards
            if piece.device == homes[piece.index[1].start // w]
        }
        per_shard = []
        for i, shard in enumerate(self.store.shards):
            piece = blocks[i][:, : shard.n_words]
            per_shard.append([self._mask_shard(piece[j], i) for j in range(k)])
        return per_shard

    def _run_per_shard(self, circ_fn, qlist, plans: ShardedPlan, block_words) -> list:
        """Heterogeneous path: each shard's plan dispatches through the one
        run_plan entrypoint against that shard's local representation."""
        from repro.obs import trace as _trace
        from repro.query.execinfo import merge_exec_infos
        from repro.query.executors import ShardContext, run_plan
        from repro.query.expr import Col
        from repro.query.index import _annotate_dispatch

        bare = self._bare_slots(qlist[0]) if len(qlist) == 1 else None
        colslot = (
            self._slot.get(qlist[0].name)
            if len(qlist) == 1 and type(qlist[0]) is Col
            else None
        )
        k = len(qlist)
        per_shard, infos = [], []
        for i, (shard, plan) in enumerate(zip(self.store.shards, plans.plans)):
            ctx = ShardContext(
                n=self.n,
                dense=shard.densify,
                store=lambda s=shard: s,
                circuit=circ_fn,
                bare=bare if k == 1 else None,
                column=colslot,
                block_words=block_words,
            )
            with _trace.span(
                "shard", shard=i, backend=getattr(plan, "algorithm", plan)
            ) as sp:
                out, info = run_plan(ctx, plan)
                if _trace.enabled and isinstance(info, dict):
                    _annotate_dispatch(sp, info)
            infos.append(info)
            if out.ndim == 1:
                out = out[None]
            # results stay device-resident; only the tiled path's internal
            # gather/scatter is host-orchestrated
            per_shard.append(
                [self._mask_shard(out[j], i) for j in range(k)]
            )
        # schema-driven merge (repro.query.execinfo): EVERY ExecInfo key is
        # folded by its registered rule -- counters sum, word-kind dicts add
        # key-wise, labels collect -- so a counter added to any backend can
        # never again be silently dropped on the sharded path
        self.last_info = {
            **merge_exec_infos(infos),
            "mode": "per_shard",
            "backends": plans.backends,
            "n_shards": self.n_shards,
            "per_shard": infos,
        }
        return per_shard

    def _mask_shard(self, out: jax.Array, i: int) -> jax.Array:
        """Tail-mask a shard's result to its slice of the universe."""
        shard = self.store.shards[i]
        mask = packed_tail_mask(shard.r, shard.n_words)
        return out if mask is None else jnp.bitwise_and(out, mask)

    def count(self, query, **kw) -> int:
        from repro.core.bitmaps import cardinality

        res = self.execute(query, **kw)
        return int(sum(int(cardinality(s)) for s in res.shards))

    # -- persistence -------------------------------------------------------
    def save(self, dirpath) -> dict:
        """Write one ``.bmsnap`` per shard plus the shard map
        (``repro.persist.shards``); returns the shard-map metadata.  Each
        device can later load ONLY its own file via
        :func:`repro.persist.load_shard`."""
        from repro.persist import save_sharded

        return save_sharded(self, dirpath)

    @classmethod
    def load(cls, dirpath, *, mesh=None, axis: str = "data",
             to_device: bool = False,
             verify: bool = False) -> "ShardedBitmapIndex":
        """Rebuild a saved sharded index, shard files mapped in place --
        no gather, no reclassification."""
        from repro.persist import load_sharded

        return load_sharded(dirpath, mesh=mesh, axis=axis,
                            to_device=to_device, verify=verify)
