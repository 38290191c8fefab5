"""Tiled circuit execution: RBMRG clean/dirty skipping for ANY compiled query.

``rbmrg_block_threshold`` (tiles.py) applies the paper's 3-case split to a
bare threshold.  This module generalises it to arbitrary compiled circuits
(``Interval`` / ``Exactly`` / ``And`` / ``Or`` compositions, multi-output
batched queries), using :meth:`Circuit.specialize`:

  1. group tiles by their *class signature* -- the tuple of per-column
     classes (all-zero / all-one / dirty) restricted to the circuit's
     support.  Tiles with the same signature need the same residual work;
  2. partially evaluate the circuit per signature.  Outputs that fold to
     constants are the case-1/case-2 tiles: written directly, zero bit
     work, zero HBM traffic;
  3. signatures whose residuals fold to the same gate DAG (for a bare
     threshold, any two signatures with equal (T - #ones, #dirty)) are
     merged into one residual *group*, capping the signature explosion.

Case-3 execution then runs on one of two engines:

  * ``engine="scan"`` (default for pack-backed stores) -- the single-scan
    device engine of :mod:`repro.kernels.tiled_scan`: O(1) kernel
    dispatches per query.  An in-kernel decode prologue materialises
    sparse/run containers straight from the device-resident packs, one
    block-unrolled ``lax.scan`` (or a scalar-prefetched Pallas grid on
    TPU) dispatches every tile block to its residual evaluator by group
    id, all-compressed tiles are resolved by a device event merge, and
    the [k, n_tiles, tile_words] result is assembled on-device -- an
    unrestricted query never round-trips through a host ``out`` array.

  * ``engine="merge"`` -- the host event-merge path: per-group gathers +
    one ``run_circuit_cached`` launch per residual group, host numpy
    ``evaluate_event_tiles`` for all-compressed tiles.  This is the
    oracle the scan engine is differentially fuzzed against, and the
    fallback for stores without a pack surface (delta overlays) or with
    paged payloads (``repro.persist.tiers`` -- whose point is touching
    only the gathered tiles, never a whole-pack device upload).

The skipping decision is made before launch -- the TPU-legal realisation
of EWAH's fast-forwarding, now for every backend that compiles to a
circuit rather than only bare thresholds.
"""
from __future__ import annotations

import os
from collections import OrderedDict

import numpy as np

from repro.core.circuits import (
    CONST0,
    CONST1,
    _EXACT_CONST_MAX_INPUTS,
    _truth_table_masks,
    Circuit,
)

from .containers import (
    CONT_DENSE,
    CONT_RUN,
    CONT_SPARSE,
    CONTAINER_CROSSOVER,
    concat_ranges,
    evaluate_event_tiles,
    truth_table_bits,
)
from .tilestore import TILE_ONE, TILE_ZERO, TileStore, _signature_counts

__all__ = ["run_tiled_circuit"]

# residual-circuit memo: (circuit structural key, signature bytes) -> result
# of Circuit.specialize.  Signatures recur heavily (clean-dominated data has
# a handful), so this makes per-query specialisation O(#distinct signatures).
# LRU: mixed workloads (many indexes / query shapes sharing the process)
# must evict the coldest entry, not dump the whole memo at the cap.
_SPECIALIZE_MEMO: OrderedDict[tuple, tuple] = OrderedDict()
_SPECIALIZE_MEMO_CAP = 4096

# per-store LRU of prepared scan plans (device index arrays + jitted
# runners), keyed by (circuit, tile selection, execution flags); see
# run_tiled_circuit
_SCAN_PLAN_CACHE_CAP = 64

# beyond this many distinct signatures the data is effectively unclassifiable
# at this granularity; the overflow tiles run the dense support circuit.
# Shared with the planner's cost model so plans price the same split the
# executor actually runs.
from repro.core.planner import _MAX_EXACT_SIGNATURES as _MAX_SIGNATURES

# device event-merge cap on residual inputs: the stacked truth-table LUT
# strides at 2**m bytes per (group, output), so residuals wider than this
# take the block-decode path instead (the host oracle allows up to
# _EXACT_CONST_MAX_INPUTS because its LUT is per group, not stacked).
_EV_MAX_INPUTS = 12


def _residual_key(res: Circuit):
    """Merge key for residual circuits: the exact truth table when the
    support is small (two residuals compute the same function iff their
    tables match -- stronger than structural identity, so e.g. every
    bare-threshold signature with equal (T - #ones, #dirty) merges no
    matter where the folded constants sat in the adder), else the
    gate-order-independent Merkle key."""
    if res.n_inputs <= _EXACT_CONST_MAX_INPUTS:
        masks, zeros, ones = _truth_table_masks(res.n_inputs)
        return (res.n_inputs, tuple(res.evaluate(masks, zeros, ones)))
    return res.semantic_key()


def _specialize(circuit: Circuit, ckey: tuple, sig_bytes: bytes, assign: dict):
    """Memoised ``circuit.specialize`` + residual merge key (LRU-evicted).

    Returns (const_outputs, residual, kept_inputs, residual_key|None).
    """
    key = (ckey, sig_bytes)
    got = _SPECIALIZE_MEMO.get(key)
    if got is not None:
        _SPECIALIZE_MEMO.move_to_end(key)
        return got
    if len(_SPECIALIZE_MEMO) >= _SPECIALIZE_MEMO_CAP:
        _SPECIALIZE_MEMO.popitem(last=False)
    const, res, kept = circuit.specialize(assign)
    got = (const, res, kept, None if res is None else _residual_key(res))
    _SPECIALIZE_MEMO[key] = got
    return got


def _resolve_engine(store, engine: str | None) -> str:
    """Pick the case-3 execution engine for ``store``.

    The scan engine needs the store-wide pack surface device-resident
    (``device_packs``) and must never be used for paged stores -- their
    point is touching only the gathered tiles.  ``REPRO_TILED_ENGINE``
    overrides for debugging/benchmarks."""
    if engine is None:
        engine = os.environ.get("REPRO_TILED_ENGINE") or None
    if engine is None:
        engine = (
            "scan"
            if (
                not getattr(store, "paged", False)
                and hasattr(store, "device_packs")
                and getattr(store, "container_kinds", None) is not None
            )
            else "merge"
        )
    if engine not in ("scan", "merge"):
        raise ValueError(f"unknown tiled engine {engine!r}")
    return engine


def run_tiled_circuit(
    store: TileStore,
    circuit: Circuit,
    *,
    block_words: int | None = None,
    interpret: bool | None = None,
    pallas: bool = True,
    tiles=None,
    engine: str | None = None,
):
    """Evaluate ``circuit`` over the store's columns with tile skipping.

    Returns ``(out, info)``: ``out`` is uint32[n_words] for a single-output
    circuit, uint32[k, n_words] otherwise; ``info`` reports the realised
    3-case split and the words actually gathered (the paper's Table 4
    work-skipped accounting, generalised).  ``info["launches"]`` counts
    device kernel dispatches -- O(1) on the scan engine (one block-scan
    dispatch + at most one event-merge dispatch), one per residual group
    on the merge engine.

    ``tiles`` restricts evaluation (and its signature specialisation /
    launch merging) to a subset of tile indices -- incremental maintenance
    work that re-runs a circuit only where inputs changed.  With it,
    ``out`` is a host ``uint32[k, len(tiles), tile_words]`` array (per
    selected tile, no tail clipping -- callers mask the partial final
    tile) and ``info["dirty_words_gathered"]`` counts only the restricted
    gather.  (``repro.stream``'s view refresh uses a leaner direct path --
    one support-residual circuit, no per-signature split -- because its
    pending tiles are typically uniformly dirty.)

    ``engine`` selects the case-3 execution strategy (``"scan"`` /
    ``"merge"``, default auto -- see :func:`_resolve_engine`).
    """
    import jax

    from repro.kernels.threshold_ssum import (
        INTERPRET,
        circuit_structural_key,
    )
    from repro.query.execinfo import make_exec_info

    if interpret is None:
        interpret = INTERPRET
    if circuit.n_inputs != store.n:
        raise ValueError(f"circuit has {circuit.n_inputs} inputs, store {store.n} columns")
    k = len(circuit.outputs)
    tw, n_tiles, nw = store.tile_words, store.n_tiles, store.n_words
    support = circuit.support()
    ckey = circuit_structural_key(circuit)
    engine = _resolve_engine(store, engine)
    scan = engine == "scan"

    restricted = tiles is not None
    sel = None
    if restricted:
        sel = np.asarray(tiles, dtype=np.int64)
        if sel.ndim != 1 or (sel.size and not
                             ((0 <= sel) & (sel < n_tiles)).all()):
            raise ValueError(f"tiles must be 1-D indices in [0, {n_tiles})")
    n_sel = int(sel.size) if restricted else n_tiles

    if scan:
        # the scan plan -- signature grouping, specialisation, decode index
        # arrays, jitted runners -- is a pure function of (store, circuit,
        # tiles).  TileStore is immutable once built, so repeat queries
        # replay the cached plan: no host pass, no device_put of plan
        # arrays, just the O(1) kernel dispatches.
        from repro.kernels import tiled_scan

        pkey = (
            ckey, sel.tobytes() if restricted else None,
            bool(interpret), bool(pallas), tiled_scan.FORCE_PALLAS_INTERPRET,
        )
        cache = store.__dict__.setdefault("_scan_plan_cache", OrderedDict())
        hit = cache.get(pkey)
        if hit is not None:
            cache.move_to_end(pkey)
            plan, tmpl = hit
            return _execute_scan_plan(
                plan, {**tmpl, "words_by_kind": dict(tmpl["words_by_kind"])}
            )
    else:
        cache = pkey = None

    # per-tile constant fill values: the scan engine broadcasts these to
    # words on-device, the merge engine expands them into the host buffer
    base_vals = np.zeros((k, n_sel), dtype=np.uint32)
    # ExecInfo (repro.query.execinfo): the one schema every backend reports
    # in; see the schema module for per-key semantics and merge rules
    info = make_exec_info(
        "tiled_fused",
        n_tiles=n_tiles,
        selected_tiles=n_sel,
        n_outputs=k,
        engine=engine,
        total_words=int(store.n * nw),
    )

    # results land on the store's device (a sharded store places each shard)
    put = getattr(store, "put", jax.numpy.asarray)

    def _finish_host(out):
        info["work_fraction"] = info["dirty_words_gathered"] / max(
            1, info["total_words"]
        )
        # roofline traffic term: gathered input words + written output words
        info["words_touched"] = info["dirty_words_gathered"] + k * nw
        if restricted:
            return out, info  # host [k, n_sel, tw], caller patches per tile
        result = out.reshape(k, -1)[:, :nw]
        return put(result[0] if k == 1 else result), info

    if not support:
        # constant circuit: no data touched at all
        const, _res, _kept = circuit.specialize({})
        for j, cval in enumerate(const):
            base_vals[j] = 0xFFFFFFFF if cval else 0
        info["const_tiles"] = n_sel
        return _finish_host(np.repeat(base_vals[:, :, None], tw, axis=2))

    # word-level signature per tile over the support (RUN counts as dirty:
    # its words need bit work whenever the tile participates at all).  Under
    # a tile restriction, "tile" arrays below index positions within ``sel``
    # (the output buffer); ``sel`` maps them back to store tile ids.
    cls = store.classes_word[support]  # [s, n_tiles], ZERO/ONE/DIRTY
    if restricted:
        cls = cls[:, sel]
    sigs, inverse = _signature_counts(cls, return_inverse=True)
    info["signatures"] = int(sigs.shape[0])

    # most-populous signatures get exact specialisation; overflow tiles run
    # the dense support circuit (correct, just less skipping)
    order = np.argsort(-np.bincount(inverse, minlength=sigs.shape[0]))
    exact = set(order[:_MAX_SIGNATURES].tolist())

    # Pass 1: specialize per signature, record the constant-folded tiles,
    # and bucket the residual work by the residual circuit's STRUCTURE.
    # Distinct signatures routinely fold to the same gate DAG (a bare
    # threshold only depends on (T - #ones, #dirty)), so merging them caps
    # the group count: one residual evaluator per structurally distinct
    # residual, not one per signature.
    overflow_tiles: list = []
    merged: dict[tuple, list] = {}  # (residual key, live outputs) -> work
    for s_id in range(sigs.shape[0]):
        tiles = np.nonzero(inverse == s_id)[0]
        if s_id not in exact:
            overflow_tiles.append(tiles)
            continue
        sig = sigs[s_id]
        assign = {i: CONST0 for i in range(store.n) if i not in support}
        for j, col in enumerate(support):
            if sig[j] == TILE_ZERO:
                assign[col] = CONST0
            elif sig[j] == TILE_ONE:
                assign[col] = CONST1
        const, res, kept, rkey = _specialize(circuit, ckey, sig.tobytes(), assign)
        for j, cval in enumerate(const):
            if cval is not None:
                base_vals[j, tiles] = 0xFFFFFFFF if cval else 0
        if res is None:
            info["const_tiles"] += int(tiles.size)
            continue
        info["case3_tiles"] += int(tiles.size)
        info["residual_signatures"] += 1
        live = tuple(j for j, cval in enumerate(const) if cval is None)
        merged.setdefault((rkey, live), [res, []])[1].append((tiles, kept))

    # the overflow residual folds only the non-support inputs; its tiles may
    # feed clean cells into kept wires (the decode prologue / dense gather
    # fills those from class metadata).  On the scan engine it rides the
    # same single dispatch as every other group.
    if overflow_tiles:
        otiles = np.concatenate(overflow_tiles)
        assign = {i: CONST0 for i in range(store.n) if i not in support}
        const, res, kept, rkey = _specialize(circuit, ckey, b"dense", assign)
        for j, cval in enumerate(const):
            if cval is not None:
                base_vals[j, otiles] = 0xFFFFFFFF if cval else 0
        if res is None:
            info["const_tiles"] += int(otiles.size)
        else:
            info["case3_tiles"] += int(otiles.size)
            live = tuple(j for j, cval in enumerate(const) if cval is None)
            if scan:
                merged.setdefault((rkey, live), [res, []])[1].append(
                    (otiles, kept)
                )
            else:
                merged[("__overflow__", live)] = [
                    res, [(otiles, kept)], "overflow",
                ]

    if scan:
        return _run_scan_pass(
            store, merged, base_vals, info, sel, restricted,
            k, tw, nw, n_sel, interpret, pallas, cache, pkey,
        )
    return _run_merge_pass(
        store, merged, base_vals, info, sel, restricted,
        k, tw, n_sel, interpret, pallas, block_words, _finish_host, put,
    )


# ---------------------------------------------------------------------------
# scan engine: O(1) dispatches via repro.kernels.tiled_scan
# ---------------------------------------------------------------------------


def _execute_scan_plan(plan, info):
    """Dispatch a (possibly cached) scan plan: broadcast the constant base,
    run the O(1) staged kernels, clip the padded tail."""
    import jax
    import jax.numpy as jnp

    k, n_sel, tw, nw = plan["k"], plan["n_sel"], plan["tw"], plan["nw"]
    restricted = plan["restricted"]
    if not plan["stages"]:
        # constants only: no device work at all
        out = np.repeat(plan["base_vals"][:, :, None], tw, axis=2)
        if restricted:
            return out, info
        result = out.reshape(k, -1)[:, :nw]
        return plan["put"](result[0] if k == 1 else result), info
    buf = jnp.asarray(plan["bv"])
    for fn, args in plan["stages"]:
        buf = fn(buf, *args)
    if restricted:
        host = np.asarray(jax.device_get(buf), np.uint32)[:, :n_sel]
        return host, info
    # device-resident result: drop the dummy tile, clip the padded tail
    result = buf[:, :n_sel].reshape(k, n_sel * tw)[:, :nw]
    return (result[0] if k == 1 else result), info


def _run_scan_pass(store, merged, base_vals, info, sel, restricted,
                   k, tw, nw, n_sel, interpret, pallas, cache, pkey):
    from repro.kernels import tiled_scan

    n_sel1 = n_sel + 1
    ck = store.container_kinds
    swc = store.storage_words_cell
    clsw = store.classes_word
    packs = store.packs
    d_index = packs["dense_index"]
    s_index, s_bounds = packs["sparse_index"], packs["sparse_bounds"]
    r_index, r_bounds = packs["run_index"], packs["run_bounds"]
    dense_pack1, sparse_pack1, run_pack1 = store.device_packs()
    D = int(packs["dense_pack"].shape[0])  # zeros sentinel tile; ones = D + 1
    S = int(sparse_pack1.shape[0]) - 1  # zero pad entry
    R = int(run_pack1.shape[0]) - 1
    dummy_out = n_sel  # flat [k, n_sel1] dummy cell: tile n_sel of output 0
    pow2, padv = tiled_scan.next_pow2, tiled_scan.pad_to

    # flatten merged groups; each group = one residual evaluator
    groups = []  # [res, live, tables|None, [(out_tiles, store_tiles, kcols)]]
    for (rkey, live), work in merged.items():
        res, entries = work[0], work[1]
        tables = (
            rkey[1]
            if isinstance(rkey, tuple) and res.n_inputs <= _EXACT_CONST_MAX_INPUTS
            else None
        )
        ents = [
            (t, sel[t] if restricted else t, np.asarray(kept, np.int64))
            for t, kept in entries
        ]
        groups.append([res, live, tables, ents])

    # ---- split each group's tiles: device event merge vs block decode ----
    stride = 1 << tiled_scan.event_key_shift(tw)
    n_ev = 0
    for g in groups:
        res, live, tables, ents = g
        m = res.n_inputs
        masks = []
        for _ot, stiles, kcols in ents:
            if tables is None or m > _EV_MAX_INPUTS or stiles.size == 0:
                masks.append(np.zeros(stiles.size, bool))
                continue
            kc = ck[kcols[:, None], stiles[None, :]]
            comp = (kc == CONT_SPARSE) | (kc == CONT_RUN)
            cw = swc[kcols[:, None], stiles[None, :]].sum(axis=0)
            masks.append(
                comp.all(axis=0) & (cw <= CONTAINER_CROSSOVER * m * tw)
            )
        g.append(masks)
        n_ev += sum(int(mk.sum()) for mk in masks)
    if n_ev and (pow2(n_ev) + 2) * stride >= 2**31:
        # event sort keys must fit int32; absurdly large event sets fall
        # back to block decode (correct, just denser staging)
        for g in groups:
            g[4] = [np.zeros_like(mk) for mk in g[4]]
        n_ev = 0

    bv = np.zeros((k, n_sel1), np.uint32)
    bv[:, :n_sel] = base_vals
    plan = {
        "bv": store.put(bv), "put": store.put, "base_vals": base_vals,
        "stages": [],
        "k": k, "n_sel": n_sel, "tw": tw, "nw": nw, "restricted": restricted,
    }

    # ---- event stage: one dispatch for every all-compressed tile ---------
    if n_ev:
        s_pack = packs["sparse_pack"]
        r_pack = packs["run_pack"]
        pos_parts, row_parts, wire_parts = [], [], []
        gid_parts, out_parts = [], []
        ev_groups = []  # (m, tables, live)
        row0 = 0
        for res, live, tables, ents, masks in groups:
            m = res.n_inputs
            if not any(mk.any() for mk in masks):
                continue
            gidx = len(ev_groups)
            ev_groups.append((m, tables, live))
            for (otiles, stiles, kcols), mk in zip(ents, masks):
                if not mk.any():
                    continue
                et, ot = stiles[mk], otiles[mk]
                ne = int(et.size)
                rows = np.arange(row0, row0 + ne, dtype=np.int64)
                kc = ck[kcols[:, None], et[None, :]]  # [m, ne]
                wg = np.broadcast_to(kcols[:, None], kc.shape)
                tg = np.broadcast_to(et[None, :], kc.shape)
                rg = np.broadcast_to(rows[None, :], kc.shape)
                wireg = np.broadcast_to(
                    np.arange(m, dtype=np.int64)[:, None], kc.shape
                )
                for kind, idx_t, bnd, pack in (
                    (CONT_SPARSE, s_index, s_bounds, s_pack),
                    (CONT_RUN, r_index, r_bounds, r_pack),
                ):
                    cm = kc == kind
                    if not cm.any():
                        continue
                    s = idx_t[wg[cm], tg[cm]]
                    cnt = bnd[s + 1] - bnd[s]
                    take = concat_ranges(bnd[s], bnd[s + 1])
                    rowv = np.repeat(rg[cm], cnt)
                    wirev = np.repeat(wireg[cm], cnt)
                    if kind == CONT_SPARSE:
                        pp = pack[take].astype(np.int64)
                        pos_parts.append(np.concatenate([pp, pp + 1]))
                    else:
                        # [e, 2] intervals -> all starts, then all ends
                        pos_parts.append(
                            pack[take].astype(np.int64).T.reshape(-1)
                        )
                    row_parts.append(np.concatenate([rowv, rowv]))
                    wire_parts.append(np.concatenate([wirev, wirev]))
                sw_ev = swc[kcols[:, None], et[None, :]]
                ew = int(sw_ev.sum())
                info["compressed_words_gathered"] += ew
                info["dirty_words_gathered"] += ew
                for kind, name in ((CONT_SPARSE, "sparse"), (CONT_RUN, "run")):
                    info["words_by_kind"][name] += int(sw_ev[kc == kind].sum())
                info["event_tiles"] += ne
                gid_parts.append(np.full(ne, gidx, np.int64))
                out_parts.append((live, rows, ot))
                row0 += ne

        rows_pad = pow2(n_ev)
        n_rows1 = rows_pad + 1
        G = len(ev_groups)
        m_max_ev = max(m for m, _t, _l in ev_groups)
        mm = 1 << m_max_ev
        k_max_ev = max(len(l) for _m, _t, l in ev_groups)
        lut = np.zeros((G + 1, k_max_ev, mm), np.uint8)
        for gi, (m, tables, _live) in enumerate(ev_groups):
            for j, tt in enumerate(tables):
                lut[gi, j, : 1 << m] = truth_table_bits(tt, m)
        gid_row = np.full(n_rows1, G, np.int32)
        gid_row[:n_ev] = np.concatenate(gid_parts)
        out_dst = np.full((k_max_ev, n_rows1), dummy_out, np.int32)
        for live, rows, ot in out_parts:
            for j, oj in enumerate(live):
                out_dst[j, rows] = oj * n_sel1 + ot
        # the kernel's scatters take ascending indices: write rows in
        # output-cell order
        out_perm = np.argsort(out_dst, axis=1, kind="stable").astype(np.int32)
        out_dst = np.take_along_axis(out_dst, out_perm, axis=1)

        # toggle merge order is pure store data: sort once here (host,
        # cached with the plan) so the kernel never pays a device sort
        pos = np.concatenate(pos_parts)
        row = np.concatenate(row_parts)
        wire = np.concatenate(wire_parts)
        keys = row * stride + pos
        order = np.argsort(keys, kind="stable")
        e_pad = pow2(max(1, keys.size))
        keys_s = padv(
            keys[order].astype(np.int32), e_pad, rows_pad * stride
        )
        mask_s = padv(
            (1 << wire[order]).astype(np.uint32), e_pad, 0
        )
        fn = tiled_scan.event_runner(k_max_ev, mm, tw)
        plan["stages"].append((fn, (
            store.put(keys_s), store.put(mask_s),
            store.put(gid_row), store.put(lut.reshape(-1)),
            store.put(out_dst), store.put(out_perm),
        )))
        info["launches"] += 1

    # ---- block stage: one dispatch for everything that needs dense work --
    bgroups = []  # (res, live, wg, tg, out_tiles)
    for res, live, _tables, ents, masks in groups:
        m = res.n_inputs
        wgs, tgs, ots = [], [], []
        for (otiles, stiles, kcols), mk in zip(ents, masks):
            dm = ~mk
            if not dm.any():
                continue
            dt = stiles[dm]
            wgs.append(np.broadcast_to(kcols[:, None], (m, dt.size)))
            tgs.append(np.broadcast_to(dt[None, :], (m, dt.size)))
            ots.append(otiles[dm])
        if ots:
            bgroups.append((
                res, live,
                np.concatenate(wgs, axis=1),
                np.concatenate(tgs, axis=1),
                np.concatenate(ots),
            ))

    if bgroups:
        circuits = tuple(b[0] for b in bgroups)
        m_max = max(c.n_inputs for c in circuits)
        k_max = max(len(b[1]) for b in bgroups)
        B = tiled_scan.pick_tile_block(
            tw, m_max, k_max, max(b[4].size for b in bgroups)
        )
        gids_p, src_p, dst_p = [], [], []
        spt_p, spc_p, spr_p = [], [], []
        rnt_p, rnc_p, rnr_p = [], [], []
        ncs = ncr = nb = 0
        for gidx, (res, live, wg, tg, ot) in enumerate(bgroups):
            m = res.n_inputs
            ng = int(ot.size)
            nb_g = -(-ng // B)
            kc = ck[wg, tg]  # [m, ng]
            cw = clsw[wg, tg]
            src = np.where(
                kc == CONT_DENSE, d_index[wg, tg],
                np.where(cw == TILE_ONE, D + 1, D),
            )
            srcp = np.full((m, nb_g * B), D, np.int64)
            srcp[:, :ng] = src
            full = np.full((m_max, nb_g * B), D, np.int64)
            full[:m] = srcp
            src_p.append(full.reshape(m_max, nb_g, B).transpose(1, 0, 2))
            for kind, idx_t, bnd, (take_p, cell_p, row_p), base_c in (
                (CONT_SPARSE, s_index, s_bounds, (spt_p, spc_p, spr_p), "s"),
                (CONT_RUN, r_index, r_bounds, (rnt_p, rnc_p, rnr_p), "r"),
            ):
                wi, ti = np.nonzero(kc == kind)
                if not wi.size:
                    continue
                flat = ((nb + ti // B) * m_max + wi) * B + ti % B
                s = idx_t[wg[wi, ti], tg[wi, ti]]
                cnt = bnd[s + 1] - bnd[s]
                take_p.append(concat_ranges(bnd[s], bnd[s + 1]))
                if base_c == "s":
                    cell_p.append(np.repeat(ncs + np.arange(s.size), cnt))
                    ncs += int(s.size)
                else:
                    cell_p.append(np.repeat(ncr + np.arange(s.size), cnt))
                    ncr += int(s.size)
                row_p.append(flat)
            tpos = np.arange(ng)
            dst_g = np.full((nb_g, k_max, B), dummy_out, np.int64)
            for j, oj in enumerate(live):
                dst_g[tpos // B, j, tpos % B] = oj * n_sel1 + ot
            dst_p.append(dst_g)
            gids_p.append(np.full(nb_g, gidx, np.int32))
            nb += nb_g
            sw_cells = swc[wg, tg]
            info["dirty_words_gathered"] += int(sw_cells.sum())
            for kind, name in (
                (CONT_DENSE, "dense"), (CONT_SPARSE, "sparse"),
                (CONT_RUN, "run"),
            ):
                kw = int(sw_cells[kc == kind].sum())
                info["words_by_kind"][name] += kw
                if kind != CONT_DENSE:
                    info["compressed_words_gathered"] += kw
            info["densified_tiles"] += ng
            info["decode_words"] += m * ng * tw

        nb_pad = pow2(nb)
        NBC = nb_pad * m_max * B
        if NBC + 1 >= 2**31:
            raise ValueError("tiled scan block plan exceeds int32 indexing")
        gids = padv(np.concatenate(gids_p), nb_pad, 0)
        cell_src = np.full(NBC + 1, D, np.int64)
        cell_src[: nb * m_max * B] = np.concatenate(src_p).reshape(-1)
        dst = np.full(nb_pad * k_max * B, dummy_out, np.int64)
        dst[: nb * k_max * B] = np.concatenate(dst_p).reshape(-1)
        perm = np.argsort(dst, kind="stable")  # ascending scatter indices

        def _decode(take_p, cell_p, row_p, nc, pad_take):
            t = np.concatenate(take_p) if take_p else np.zeros(0, np.int64)
            c = np.concatenate(cell_p) if cell_p else np.zeros(0, np.int64)
            rr = np.concatenate(row_p) if row_p else np.zeros(0, np.int64)
            if nc:
                # renumber the decoded cells in block-row order, so the
                # kernel's row scatters take ascending indices
                order = np.argsort(rr, kind="stable")
                cnt = np.bincount(c, minlength=nc)
                start = np.concatenate([[0], np.cumsum(cnt)[:-1]])
                t = t[concat_ranges(start[order], start[order] + cnt[order])]
                c = np.repeat(np.arange(nc), cnt[order])
                rr = rr[order]
            nc1 = pow2(max(1, nc)) + 1
            size = pow2(max(1, t.size))
            return (
                store.put(padv(t.astype(np.int32), size, pad_take)),
                store.put(padv(c.astype(np.int32), size, nc1 - 1)),
                store.put(padv(rr.astype(np.int32), nc1, NBC)),
            )

        spt, spc, spr = _decode(spt_p, spc_p, spr_p, ncs, S)
        rnt, rnc, rnr = _decode(rnt_p, rnc_p, rnr_p, ncr, R)
        use_pallas = pallas and (
            not interpret or tiled_scan.FORCE_PALLAS_INTERPRET
        )
        fn = tiled_scan.block_runner(
            circuits, m_max, k_max, tw, use_pallas, interpret
        )
        info["kernel"] = "pallas" if use_pallas else "xla"
        plan["stages"].append((fn, (
            store.put(gids), dense_pack1,
            store.put(cell_src.astype(np.int32)),
            sparse_pack1, spt, spc, spr,
            run_pack1, rnt, rnc, rnr,
            store.put(dst[perm].astype(np.int32)),
            store.put(perm.astype(np.int32)),
        )))
        info["launches"] += 1

    info["work_fraction"] = info["dirty_words_gathered"] / max(
        1, info["total_words"]
    )
    info["words_touched"] = info["dirty_words_gathered"] + k * nw
    cache[pkey] = (plan, {**info, "words_by_kind": dict(info["words_by_kind"])})
    while len(cache) > _SCAN_PLAN_CACHE_CAP:
        cache.popitem(last=False)
    return _execute_scan_plan(plan, info)


# ---------------------------------------------------------------------------
# merge engine: host event merge + one launch per residual group (oracle)
# ---------------------------------------------------------------------------


def _run_merge_pass(store, merged, base_vals, info, sel, restricted,
                    k, tw, n_sel, interpret, pallas, block_words,
                    _finish_host, put):
    import jax

    from repro.kernels.threshold_ssum import run_circuit_cached

    out = np.repeat(base_vals[:, :, None], tw, axis=2)

    # Per merged group, split its case-3 tiles by representation.  Tiles
    # whose residual inputs are ALL compressed containers (sparse / run)
    # -- and whose compressed payload undercuts the dense gather by the
    # crossover -- are evaluated container-natively: boundary events merged
    # position-list-style against the residual's exact truth table (the
    # paper's MergeOpt/ScanCount view of the same query).  The rest densify
    # per tile (sparse/run cells decompressed on the fly, never a
    # store-wide expansion) into one gather + one cached kernel per group.
    container_native = hasattr(store, "gather_events") and getattr(
        store, "container_kinds", None
    ) is not None
    ck = store.container_kinds if container_native else None
    swc = store.storage_words_cell if container_native else None
    # with no compressed tile anywhere (containers off, or purely dense
    # data) the legacy device-side gather path is byte-identical and keeps
    # the working set on-device -- no host round trip per query
    # paged stores (repro.persist.tiers) must never trigger the whole-pack
    # device upload: their point is touching only the gathered tiles
    all_dense = not getattr(store, "paged", False) and (
        not container_native or not (ck > CONT_DENSE).any()
    )
    for (rkey, live), work in merged.items():
        res, entries = work[0], work[1]
        overflow = len(work) > 2
        m = res.n_inputs
        # exact truth tables exist for small residuals; _residual_key
        # computed them already (rkey = (n_inputs, per-output tables))
        tables = (
            rkey[1]
            if not overflow
            and container_native
            and m <= _EXACT_CONST_MAX_INPUTS
            else None
        )
        ev_rows, ev_pos, ev_wires = [], [], []
        ev_out_tiles: list = []
        dense_out_tiles: list = []
        dense_gathers: list = []
        n_ev = 0
        for tiles, kept in entries:
            stiles = sel[tiles] if restricted else tiles
            kcols = np.asarray(kept, np.int64)
            if tables is not None:
                kinds_cell = ck[kcols[:, None], stiles[None, :]]
                comp = (kinds_cell == CONT_SPARSE) | (kinds_cell == CONT_RUN)
                cwords = swc[kcols[:, None], stiles[None, :]].sum(axis=0)
                ev_mask = comp.all(axis=0) & (
                    cwords <= CONTAINER_CROSSOVER * m * tw
                )
            else:
                ev_mask = np.zeros(tiles.size, bool)
            if ev_mask.any():
                et = stiles[ev_mask]
                ne = int(et.size)
                cell, pos = store.gather_events(
                    np.repeat(kcols, ne), np.tile(et, m)
                )
                ev_rows.append(n_ev + cell % ne)
                ev_pos.append(pos)
                ev_wires.append(cell // ne)
                ev_out_tiles.append(tiles[ev_mask])
                n_ev += ne
                sw_ev = swc[kcols[:, None], et[None, :]]
                ew = int(sw_ev.sum())
                info["compressed_words_gathered"] += ew
                info["dirty_words_gathered"] += ew
                kc_ev = kinds_cell[:, ev_mask]
                for kind, name in ((CONT_SPARSE, "sparse"), (CONT_RUN, "run")):
                    info["words_by_kind"][name] += int(
                        sw_ev[kc_ev == kind].sum()
                    )
            dmask = ~ev_mask
            if dmask.any():
                dt = stiles[dmask]
                nd = int(dt.size)
                # residual input order follows each signature's kept-column
                # order, so tiles from different signatures feed the same
                # kernel wires
                if overflow:
                    # dense fallback: full support rows for these tiles
                    dense = np.asarray(
                        jax.device_get(store.densify()), dtype=np.uint32
                    )
                    pad = store.n_tiles * tw - store.n_words
                    if pad:
                        dense = np.pad(dense, ((0, 0), (0, pad)))
                    dense = dense.reshape(store.n, store.n_tiles, tw)
                    cells = dense[kcols[:, None], dt[None, :]]
                    dense_gathers.append(cells.reshape(m, nd * tw))
                    # every overflow cell reads dense-expanded words
                    info["words_by_kind"]["dense"] += m * nd * tw
                elif all_dense:
                    # device path: index rows of the packed dirty array,
                    # gather on-device right before the kernel launch
                    dense_gathers.append(store.dirty_index[kept][:, dt])
                    # kind breakdown must not depend on the container
                    # surface being present: the device gather reads
                    # dense(-equivalent) words either way
                    info["words_by_kind"]["dense"] += m * nd * tw
                else:
                    cells = store.gather_cells(
                        np.repeat(kcols, nd), np.tile(dt, m)
                    )
                    if swc is not None:
                        sw_dt = swc[kcols[:, None], dt[None, :]]
                        kc_dt = ck[kcols[:, None], dt[None, :]]
                        for kind, name in (
                            (CONT_DENSE, "dense"),
                            (CONT_SPARSE, "sparse"),
                            (CONT_RUN, "run"),
                        ):
                            kw = int(sw_dt[kc_dt == kind].sum())
                            info["words_by_kind"][name] += kw
                            if kind != CONT_DENSE:
                                info["compressed_words_gathered"] += kw
                    else:
                        info["words_by_kind"]["dense"] += m * nd * tw
                    dense_gathers.append(cells.reshape(m, nd * tw))
                dense_out_tiles.append(tiles[dmask])
        if n_ev:
            got = evaluate_event_tiles(
                np.concatenate(ev_rows),
                np.concatenate(ev_pos),
                np.concatenate(ev_wires),
                n_ev,
                tw,
                tables,
                m,
            )
            etiles = np.concatenate(ev_out_tiles)
            out[np.asarray(live)[:, None], etiles[None, :]] = got
            info["event_tiles"] += n_ev
        if dense_gathers:
            tiles = np.concatenate(dense_out_tiles)
            if all_dense and not overflow:
                rows = np.concatenate(dense_gathers, axis=1)  # [m, nd]
                gathered = store.dirty[rows.reshape(-1)].reshape(m, -1)
            else:
                gathered = put(
                    np.concatenate(dense_gathers, axis=1)
                )
            info["dirty_words_gathered"] += int(gathered.size)
            info["densified_tiles"] += int(tiles.size)
            info["launches"] += 1
            info["kernel"] = "pallas" if pallas else "xla"
            got = run_circuit_cached(
                gathered, res,
                block_words=block_words, interpret=interpret, pallas=pallas,
            )
            got = np.asarray(jax.device_get(got), dtype=np.uint32)
            if got.ndim == 1:
                got = got[None]
            out[np.asarray(live)[:, None], tiles[None, :]] = got.reshape(
                len(live), tiles.size, tw
            )

    return _finish_host(out)
