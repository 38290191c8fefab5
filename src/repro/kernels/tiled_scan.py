"""Single-scan tiled execution: in-kernel container decode + launch collapse.

The legacy ``run_tiled_circuit`` pass dispatched one gather + one
``run_circuit_cached`` launch per structurally distinct residual circuit,
resolved compressed-only tiles with a *host* numpy event merge, and wrote
every partial result back into a host ``out`` array.  At small scale those
per-group launches and host round trips dominate wall time even when the
words-touched model says tiled execution should win.

This module collapses the whole case-3 workload into O(1) device
dispatches:

  * **Block stage** -- every tile that needs dense bit work is assigned to
    a fixed-size *block* of ``B`` tiles belonging to one residual group.
    A decode prologue materialises each residual-input cell directly from
    the store's device-resident container packs: dense cells are rows of
    the (sentinel-augmented) dense pack, sparse cells bit-scatter their
    uint16 position lists, run cells toggle-scatter their interval
    endpoints and fill with a branch-free prefix-XOR -- the device port of
    :func:`repro.storage.containers.rasterize_toggles`.  The blocks are
    then evaluated by ONE kernel: a block-unrolled ``lax.scan`` over
    (group id, block) pairs whose body ``lax.switch``-es into the right
    residual evaluator (XLA path, default off-TPU), or a Pallas grid
    kernel with a scalar-prefetched group-id vector (TPU path -- the grid
    auto-pipelines the block DMA, i.e. double-buffered HBM->VMEM).

  * **Event stage** -- tiles whose residual inputs are ALL sparse/run
    containers (and whose payload undercuts the dense gather) skip block
    decode entirely: their boundary events are sorted on device
    (``lax.sort``), per-input masks XOR-accumulated (associative scan),
    each segment's input combination mapped through stacked per-group
    truth-table LUTs, and value changes rasterized back to packed words
    -- the device port of
    :func:`repro.storage.containers.evaluate_event_tiles`, all groups in
    one dispatch.

  * **Output assembly** -- both stages scatter into one device-resident
    ``[k, n_tiles + 1, tile_words]`` buffer (slot ``n_tiles`` is a dummy
    target for padding lanes) seeded by broadcasting the per-tile
    constant-fold values, so unrestricted queries never round-trip
    through a host ``out`` array.

Compile time on the TPU shapes three choices below: the block stage's
toggle masks are scattered straight into 2-D ``[rows, tw]`` buffers,
the prefix-XOR carries word parities with a small matmul instead of a
scan (see :func:`_prefix_xor_words`), and every row scatter (``.set``)
is declared ``indices_are_sorted`` -- an unsorted row scatter compiles
in time that grows with its index count.  The plan builder
(``repro.storage.tiled``) orders cells, toggles and output rows on the
host, once per cached plan, so the indices are non-decreasing by
construction.  The bit scatter-adds, whose (row, word) indices repeat
whenever two toggles share a word, carry no such hint: with it, a TPU v5e
returned wrong words once a stage reached 65537 rows.

Carry-free scatter invariants (JAX has no XOR-scatter, so every scatter
below must be provably collision-free under ``.at[].add``):

  * sparse positions are sorted and distinct per cell -> distinct bits;
  * run containers store *maximal* intervals, so the 2i endpoints of a
    cell strictly increase -> distinct toggle positions;
  * the event stage only emits a toggle at the LAST event of each
    (row, position) run after the sort, so toggle positions are distinct
    per row (duplicate-position cancellation is resolved by comparing
    against the value *before* the run, found by a forward-fill of the
    run-start index).

Everything data-dependent is padded to power-of-two sizes by the plan
builder (``repro.storage.tiled``), so jit traces are shared across
queries that differ only in tile counts.
"""
from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import circuits as _ckt
from repro.obs import REGISTRY as _OBS

__all__ = [
    "block_runner",
    "event_runner",
    "clear_scan_runners",
    "next_pow2",
    "pad_to",
    "pick_tile_block",
]

_U32 = jnp.uint32

# dispatch accounting on the process registry (no-op until obs.enable()):
# launches per stage kind, words the decode prologue stages, and event
# toggles merged -- the device-side counterpart of ExecInfo's per-query
# numbers, aggregated process-wide across every store and query
_LAUNCHES = _OBS.counter(
    "repro_kernel_launches_total", "Device kernel dispatches", ("stage",),
)
_DECODE_WORDS = _OBS.counter(
    "repro_kernel_decode_words_total",
    "Dense-equivalent words staged by the in-kernel container decode",
)
_EVENT_TOGGLES = _OBS.counter(
    "repro_kernel_event_toggles_total",
    "Boundary toggles merged by the event stage",
)
# label keys pre-bound once: the launch loop incs these per dispatch
_LAUNCH_BLOCK = _LAUNCHES.bind(stage="block")
_LAUNCH_EVENT = _LAUNCHES.bind(stage="event")
_DECODE_WORDS_B = _DECODE_WORDS.bind()
_EVENT_TOGGLES_B = _EVENT_TOGGLES.bind()

#: test hook: evaluate the block stage through the Pallas grid kernel even
#: in interpret mode (CPU), pinning the grid kernel against the XLA scan.
FORCE_PALLAS_INTERPRET = False

# compiled stage runners, keyed by (stage, circuit structures, static dims).
# Shape variation within a key is handled by jax.jit's own cache; padding
# to powers of two bounds how many shapes each key sees.
_RUNNERS: OrderedDict = OrderedDict()
_RUNNERS_CAP = 256


def clear_scan_runners() -> None:
    """Drop the compiled stage runners (wired into clear_compiled_cache)."""
    _RUNNERS.clear()


def next_pow2(x: int) -> int:
    """Smallest power of two >= max(x, 1)."""
    return 1 << max(0, int(x) - 1).bit_length()


def pad_to(a: np.ndarray, size: int, fill) -> np.ndarray:
    """``a`` grown to ``size`` along axis 0, new entries = ``fill``."""
    out = np.full((size,) + a.shape[1:], fill, a.dtype)
    out[: a.shape[0]] = a
    return out


def pick_tile_block(tile_words: int, m_max: int, k_max: int,
                    max_group_tiles: int,
                    vmem_budget_bytes: int = 2 * 1024 * 1024) -> int:
    """Tiles per block: lane-sized (1024 words) but shrunk so one block's
    input+output rows fit the VMEM budget, and never wider than the
    largest group needs."""
    from repro.kernels.threshold_ssum import LANE_WORDS

    b = max(1, LANE_WORDS // tile_words)
    while b > 1 and (m_max + k_max) * b * tile_words * 8 > vmem_budget_bytes:
        b //= 2
    return max(1, min(b, next_pow2(max_group_tiles)))


def _bit(pos):
    """1 << (pos % 32) as uint32 (pos: non-negative int32 array)."""
    return _U32(1) << (pos % 32).astype(_U32)


def _prefix_xor_words(t):
    """Toggle masks uint32[rows, tw] -> filled words uint32[rows, tw].

    Device port of the tail of ``rasterize_toggles``: prefix-XOR within
    each word by doubling shifts, then carry word parities across the row.
    The carry is a count of earlier odd words, taken as a 0/1 matmul with a
    strictly upper-triangular matrix (exact: counts stay below 2**8): an
    associative scan here made the TPU compiler take about half a minute
    per decode at 2**26 rows.  A toggle at the span boundary changes no
    bit of the tile, so callers drop it (``mode="drop"`` on the scatter
    that builds ``t``)."""
    for sh in (1, 2, 4, 8, 16):
        t = t ^ (t << _U32(sh))
    tw = t.shape[1]
    par = (t >> _U32(31)).astype(jnp.bfloat16)
    earlier = jnp.triu(jnp.ones((tw, tw), jnp.bfloat16), 1)
    odd = jnp.dot(par, earlier, preferred_element_type=jnp.float32)
    return t ^ ((odd.astype(_U32) & _U32(1)) * _U32(0xFFFFFFFF))


def gather_tiles(pack, idx, tw: int):
    """Tiles ``idx`` (int32[n]) of a dense pack stored ``pack.shape[1] //
    tw`` tiles to a row (``TileStore.device_packs``) -> uint32[n, tw]."""
    per_row = pack.shape[1] // tw
    if per_row == 1:
        return pack[idx]
    rows = pack[idx // per_row].reshape(-1, per_row, tw)
    slot = (idx % per_row)[:, None]
    out = rows[:, 0]
    for s in range(1, per_row):
        out = jnp.where(slot == s, rows[:, s], out)
    return out


def _expand_base(base, tw):
    """Per-tile constant words [k, n_sel1] -> full buffer [k, n_sel1, tw]."""
    if base.ndim == 2:
        return jnp.broadcast_to(base[:, :, None], base.shape + (tw,))
    return base


def block_runner(circuits: tuple, m_max: int, k_max: int, tw: int,
                 use_pallas: bool, interpret: bool):
    """Compiled block stage for a tuple of residual circuits.

    Returns ``fn(base, gids, dense_pack1, cell_src, sparse_pack1, sp_take,
    sp_cell, sp_rows, run_pack1, rn_take, rn_cell, rn_rows, dst, perm)``
    where

    * ``base``: uint32[k, n_sel1] constant fill values (expanded in-kernel)
      or uint32[k, n_sel1, tw] (already-assembled buffer from a previous
      stage); returns the updated [k, n_sel1, tw] buffer;
    * ``gids``: int32[nb] residual-group id per block;
    * ``dense_pack1``: the dense pack + zeros/ones sentinel tiles, stored
      as :meth:`TileStore.device_packs` lays it out (see
      :func:`gather_tiles`);
    * ``cell_src``: int32[nb * m_max * B + 1] dense-pack tile per block cell
      (compressed cells point at the zeros sentinel and are overwritten by
      the decode prologue; the trailing entry is the scatter dummy row);
    * ``sp_take``/``sp_cell``: sparse payload take-indices and decode-row
      ids (non-decreasing, a cell's positions ascending); ``sp_rows``:
      block-cell row per decode row, ascending (dummy -> sentinel);
    * ``rn_take``/``rn_cell``/``rn_rows``: same for run intervals;
    * ``dst``: int32[nb * k_max * B] flat output cell per block lane,
      ascending; ``perm``: the block lane written to each ``dst`` entry.
    """
    from repro.kernels.threshold_ssum import circuit_structural_key

    key = (
        "block",
        tuple(circuit_structural_key(c) for c in circuits),
        m_max, k_max, tw, bool(use_pallas), bool(interpret),
    )
    fn = _RUNNERS.get(key)
    if fn is not None:
        _RUNNERS.move_to_end(key)
        return fn

    def _eval_block(g, x):
        btw = x.shape[-1]
        zeros = jnp.zeros((btw,), _U32)
        ones = jnp.full((btw,), 0xFFFFFFFF, _U32)

        def _branch(circ):
            def f(xb):
                rows = [xb[i] for i in range(circ.n_inputs)]
                outs = circ.evaluate(rows, zeros=zeros, ones=ones)
                outs = list(outs) + [zeros] * (k_max - len(outs))
                return jnp.stack(outs)

            return f

        return jax.lax.switch(g, [_branch(c) for c in circuits], x)

    def _pallas_eval(gids, x):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        nb, _, btw = x.shape

        def _kernel(gids_ref, in_ref, out_ref):
            g = gids_ref[pl.program_id(0)]
            out_ref[0] = _eval_block(g, in_ref[0])

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[pl.BlockSpec((1, m_max, btw), lambda b, g: (b, 0, 0))],
            out_specs=pl.BlockSpec((1, k_max, btw), lambda b, g: (b, 0, 0)),
        )
        return pl.pallas_call(
            _kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((nb, k_max, btw), _U32),
            interpret=interpret,
        )(gids, x)

    def tiled_block(base, gids, dense_pack1, cell_src,
                    sparse_pack1, sp_take, sp_cell, sp_rows,
                    run_pack1, rn_take, rn_cell, rn_rows, dst, perm):
        base = _expand_base(base, tw)
        nb = gids.shape[0]
        B = (cell_src.shape[0] - 1) // (nb * m_max)
        btw = B * tw
        # decode prologue: every residual-input cell materialised into the
        # block buffer straight from the container packs
        blocks = gather_tiles(dense_pack1, cell_src, tw)  # [nb*m_max*B + 1, tw]
        ncs1 = sp_rows.shape[0]
        pos = sparse_pack1[sp_take].astype(jnp.int32)
        sw = jnp.zeros((ncs1, tw), _U32).at[sp_cell, pos // 32].add(
            _bit(pos)
        )
        blocks = blocks.at[sp_rows].set(sw, indices_are_sorted=True)
        ncr1 = rn_rows.shape[0]
        iv = run_pack1[rn_take].astype(jnp.int32)
        t = jnp.zeros((ncr1, tw), _U32)
        for end in (0, 1):  # interval starts, then ends
            t = t.at[rn_cell, iv[:, end] // 32].add(
                _bit(iv[:, end]), mode="drop"
            )
        rw = _prefix_xor_words(t)
        blocks = blocks.at[rn_rows].set(rw, indices_are_sorted=True)
        x = blocks[:-1].reshape(nb, m_max, btw)
        if use_pallas:
            ys = _pallas_eval(gids, x)
        else:
            def body(carry, gx):
                g, xb = gx
                return carry, _eval_block(g, xb)

            _, ys = jax.lax.scan(body, None, (gids, x))
        out = base.reshape(-1, tw).at[dst].set(
            ys.reshape(-1, tw)[perm], indices_are_sorted=True
        )
        return out.reshape(base.shape)

    jitted = jax.jit(tiled_block)

    def fn(base, gids, dense_pack1, cell_src, *rest):
        if _OBS.enabled:
            _LAUNCH_BLOCK.inc(1)
            _DECODE_WORDS_B.inc((cell_src.shape[0] - 1) * tw)
        return jitted(base, gids, dense_pack1, cell_src, *rest)

    if len(_RUNNERS) >= _RUNNERS_CAP:
        _RUNNERS.popitem(last=False)
    _RUNNERS[key] = fn
    return fn


def event_key_shift(tw: int) -> int:
    """Bits below the row in an event sort key: toggle positions run from 0
    to ``tw * 32``.  The stride is a power of two, so the kernel splits
    keys with a shift and a mask and never divides."""
    return (tw * 32).bit_length()


def event_runner(k_max: int, mm: int, tw: int):
    """Compiled event stage: ``mm = 2 ** m_max`` is the stacked-LUT stride.

    ``fn(base, keys, mask, gid_row, lut, out_dst, out_perm)``:

    * ``keys``: int32[e_pad] toggle sort keys, ``row << event_key_shift(tw)
      | pos`` -- PRE-SORTED ascending at plan-build time (the merge order is
      pure store data, so the host sorts once per cached plan instead of
      the device sorting per query); pad entries carry the dummy row's
      key, which exceeds every real key;
    * ``mask``: uint32[e_pad] per-toggle wire bit (``1 << wire``), riding
      the same order as ``keys``; pad entries are 0 (XOR no-op);
    * ``gid_row``: int32[n_rows1] event-group ordinal per row (dummy rows
      point at the zero group appended to ``lut``);
    * ``lut``: uint8[(G + 1) * k_max * mm] stacked truth tables,
      ``lut[(g * k_max + j) * mm + combo]`` = output j of group g on input
      combination ``combo``; entry 0 of each table is the background
      (all-inputs-zero) value;
    * ``out_dst``: int32[k_max, n_rows1] flat output cell per (output
      slot, event row), each row ascending, dummies -> the buffer's dummy
      tile; ``out_perm``: the event row written to each ``out_dst`` entry.
    """
    key = ("event", k_max, mm, tw)
    fn = _RUNNERS.get(key)
    if fn is not None:
        _RUNNERS.move_to_end(key)
        return fn

    shift = event_key_shift(tw)

    def tiled_event(base, keys, mask, gid_row, lut, out_dst, out_perm):
        base = _expand_base(base, tw)
        n_rows1 = gid_row.shape[0]
        e = keys.shape[0]
        xacc = jax.lax.associative_scan(jnp.bitwise_xor, mask)
        rows_s = keys >> shift
        pos_s = keys & ((1 << shift) - 1)
        iota = jnp.arange(e, dtype=jnp.int32)
        prev_key = jnp.concatenate(
            [jnp.full((1,), -1, keys.dtype), keys[:-1]]
        )
        starts = rows_s != prev_key >> shift
        firsts = keys != prev_key
        lasts = jnp.concatenate(
            [keys[1:] != keys[:-1], jnp.ones((1,), bool)]
        )
        pxa = jnp.concatenate([jnp.zeros((1,), _U32), xacc[:-1]])
        sidx = jax.lax.associative_scan(
            jnp.maximum, jnp.where(starts, iota, -1)
        )
        # combo of the segment each event closes = running XOR minus the
        # carry-in from before this row (forward-filled row-start lookup)
        combo = ((xacc ^ pxa[sidx]) & _U32(mm - 1)).astype(jnp.int32)
        fidx = jax.lax.associative_scan(
            jnp.maximum, jnp.where(firsts, iota, -1)
        )
        g_ev = gid_row[rows_s]
        base_flat = base.reshape(-1, tw)
        for j in range(k_max):
            lb = (g_ev * k_max + j) * mm
            vals = lut[lb + combo]
            pv = jnp.concatenate([jnp.zeros((1,), lut.dtype), vals[:-1]])
            pv = jnp.where(starts, lut[lb], pv)  # row start -> background
            # duplicate toggles at one position cancel: only the LAST event
            # of a (row, pos) run may toggle, and only if the value changed
            # relative to before the run
            tog = lasts & (vals != pv[fidx])
            tval = jnp.where(tog, _bit(pos_s), _U32(0))
            t = jnp.zeros((n_rows1, tw), _U32).at[rows_s, pos_s // 32].add(
                tval, mode="drop"
            )
            words = _prefix_xor_words(t)
            bg = lut[(gid_row * k_max + j) * mm].astype(bool)
            words = jnp.where(bg[:, None], ~words, words)
            base_flat = base_flat.at[out_dst[j]].set(
                words[out_perm[j]], indices_are_sorted=True
            )
        return base_flat.reshape(base.shape)

    jitted = jax.jit(tiled_event)

    def fn(base, keys, *rest):
        if _OBS.enabled:
            _LAUNCH_EVENT.inc(1)
            _EVENT_TOGGLES_B.inc(keys.shape[0])
        return jitted(base, keys, *rest)

    if len(_RUNNERS) >= _RUNNERS_CAP:
        _RUNNERS.popitem(last=False)
    _RUNNERS[key] = fn
    return fn
