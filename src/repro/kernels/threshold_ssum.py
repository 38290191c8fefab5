"""Fused sideways-sum threshold kernel (Pallas, TPU target).

The paper's circuit algorithms are "horizontal": W bits of every input are
combined into W output bits using ~5N bitwise ops (4.4.3).  Evaluated as
composed jnp ops, every intermediate bit-plane round-trips through HBM --
~5N extra bitmap reads/writes.  The fused kernel streams one
(N, block_words) tile of packed words HBM->VMEM, evaluates the whole
sideways-sum + comparator network on VMEM values (VPU bitwise ops over
uint32 lanes), and writes a single (block_words,) output tile.

HBM traffic drops from ~(1 + 2*5)x input bytes to ~(1 + 1/N)x -- the
arithmetic intensity of the circuit (~5 VPU ops / 4 B) stays memory-bound,
so traffic is the roofline term and the fusion is worth ~an order of
magnitude (``benchmarks/kernel_bench.py`` models the traffic).

Tiling: the word axis is split into ``block_words`` chunks (grid dim 0);
the full N axis rides along in VMEM because every level of the adder needs
all lanes of the previous level.  VMEM footprint ~= (N input rows + ~N/2
live intermediates) * block_words * 4 B; ``pick_block_words`` sizes the
block to a VMEM budget and keeps it a multiple of 1024 words (8 * 128
lanes * 32 bits = one packed VPU tile of bit positions).  This is the
composite runner (``run_circuit_pallas``), which reads every column.

A bare threshold names a member subset: ``threshold_pallas`` reads its
members in place from the store's plane view (one tile-aligned plane per
column, below), the slots riding in as a scalar prefetch, so no gather,
broadcast or pad precedes the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import circuits as _ckt

LANE_WORDS = 1024  # words per (8,128) int32 vreg tile

# Off the TPU (the CPU test path) the kernels run with interpret=True: the
# Pallas interpreter executes the kernel body in Python.  On a TPU backend
# the same call lowers through Mosaic.
INTERPRET = jax.default_backend() != "tpu"


def pick_block_words(n: int, n_words: int, vmem_budget_bytes: int = 4 * 1024 * 1024) -> int:
    """Largest lane-aligned block s.t. ~2N live rows fit in the VMEM budget."""
    live_rows = max(2 * n, 4)
    bw = vmem_budget_bytes // (live_rows * 4)
    bw = max(LANE_WORDS, (bw // LANE_WORDS) * LANE_WORDS)
    total = ((n_words + LANE_WORDS - 1) // LANE_WORDS) * LANE_WORDS
    return min(bw, total)


def _circuit_kernel(in_ref, out_ref, *, circuit: _ckt.Circuit, n: int):
    rows = [in_ref[i, :] for i in range(n)]
    outs = circuit.evaluate(
        rows,
        zeros=jnp.zeros_like(rows[0]),
        ones=jnp.full_like(rows[0], 0xFFFFFFFF),
    )
    for j, out in enumerate(outs):
        out_ref[j, :] = out


def run_circuit_pallas(
    bitmaps: jax.Array,
    circuit: _ckt.Circuit,
    *,
    block_words: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Evaluate an arbitrary (multi-output) circuit fused in VMEM.

    bitmaps: uint32[N, n_words] with N == circuit.n_inputs.  Returns
    uint32[n_words] for a single-output circuit, uint32[k, n_words]
    otherwise -- the batched-query path writes every output per tile while
    the inputs are resident, so k queries cost one HBM sweep, not k.
    """
    bitmaps = jnp.asarray(bitmaps, jnp.uint32)
    n, n_words = bitmaps.shape
    if circuit.n_inputs != n:
        raise ValueError(f"circuit has {circuit.n_inputs} inputs, bitmaps {n}")
    k = len(circuit.outputs)
    if block_words is None:
        # budget VMEM for the k output rows of the batched-query path too,
        # not just the ~2N live input/intermediate rows
        block_words = pick_block_words(n + k, n_words)
    padded = pl.cdiv(n_words, block_words) * block_words
    if padded != n_words:
        bitmaps = jnp.pad(bitmaps, ((0, 0), (0, padded - n_words)))
    grid = (padded // block_words,)
    out = pl.pallas_call(
        functools.partial(_circuit_kernel, circuit=circuit, n=n),
        grid=grid,
        in_specs=[pl.BlockSpec((n, block_words), lambda i: (0, i))],
        out_specs=pl.BlockSpec((k, block_words), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, padded), jnp.uint32),
        interpret=interpret,
    )(bitmaps)
    out = out[:, :n_words]
    return out[0] if k == 1 else out


# ---------------------------------------------------------------------------
# Structural jit cache: the tiled executor runs many small *data-dependent*
# residual circuits (one per tile-class signature), so caching by Python
# function identity (as jax.jit does) would recompile every call.  Keying by
# the circuit's structure lets repeated signatures -- across tiles, queries,
# and indexes -- share one compiled kernel.
# ---------------------------------------------------------------------------

_CIRCUIT_RUNNERS: dict[tuple, object] = {}
_CIRCUIT_RUNNERS_CAP = 1024  # residual circuits are data-dependent; bound them


def clear_circuit_runners() -> None:
    """Drop the structural jit cache (wired into query.clear_compiled_cache)."""
    _CIRCUIT_RUNNERS.clear()


def circuit_structural_key(circuit: _ckt.Circuit) -> tuple:
    """Hashable identity of a gate DAG (used to cache compiled evaluators)."""
    return (circuit.n_inputs, tuple(circuit.ops), tuple(circuit.outputs))


def circuit_runner(
    circuit: _ckt.Circuit,
    *,
    block_words: int | None = None,
    interpret: bool = False,
    pallas: bool = True,
):
    """The jitted evaluator of ``circuit``, cached by circuit structure.

    ``pallas=True`` lowers through :func:`run_circuit_pallas` (fused VMEM
    evaluation); otherwise the gate DAG is evaluated as straight-line jnp
    bitwise code under one jit.  The runner maps uint32[N, n_words] to
    uint32[n_words] (single output) or uint32[k, n_words].
    """
    key = (circuit_structural_key(circuit), block_words, interpret, pallas)
    fn = _CIRCUIT_RUNNERS.get(key)
    if fn is None:
        if len(_CIRCUIT_RUNNERS) >= _CIRCUIT_RUNNERS_CAP:
            _CIRCUIT_RUNNERS.clear()
        if pallas:
            def run(bm, _c=circuit):
                return run_circuit_pallas(
                    bm, _c, block_words=block_words, interpret=interpret
                )
        else:
            def run(bm, _c=circuit):
                outs = _c.evaluate([bm[i] for i in range(bm.shape[0])])
                return outs[0] if len(outs) == 1 else jnp.stack(outs)
        fn = jax.jit(run)
        _CIRCUIT_RUNNERS[key] = fn
    return fn


def run_circuit_cached(
    bitmaps: jax.Array,
    circuit: _ckt.Circuit,
    *,
    block_words: int | None = None,
    interpret: bool = False,
    pallas: bool = True,
) -> jax.Array:
    """Evaluate ``circuit`` via :func:`circuit_runner`'s cached runner."""
    return circuit_runner(
        circuit, block_words=block_words, interpret=interpret, pallas=pallas
    )(bitmaps)


# ---------------------------------------------------------------------------
# Bare thresholds over the plane view: members read where they lie.
#
# A TPU lays a 2-D uint32 array out in (8, 128) tiles, so one column of the
# dense [N, n_words] view is not contiguous, and Mosaic refuses a block of
# one row of it ("slice shape along dimension 0 must be aligned to tiling
# (8)").  The plane view holds each column as its own tile-aligned plane,
# uint32[N, P, 8, 128]: slab p of column c is words [1024p, 1024p + 1024)
# of the column, one (8, 128) tile, so a block of slabs is one contiguous
# DMA and the flat result is a bitcast of the kernel's output.  The member
# slots ride in as a scalar prefetch and pick each input's plane in its
# index map: nothing is gathered, broadcast or padded before the kernel.
# ---------------------------------------------------------------------------

SLAB = (8, 128)  # one (8, 128) uint32 tile: LANE_WORDS words of a column
#: slabs per grid block at most; the plane view is padded to a multiple of
#: it (or to a power of two below it), so every block size divides it
MAX_PLANE_BLOCK = 128
#: VMEM for the double-buffered member and output blocks (v5e's default
#: scoped VMEM is 16 MiB; the circuit's values live in vregs, one slab at
#: a time)
_PLANE_VMEM_BYTES = 12 * 1024 * 1024


def plane_count(n_words: int) -> int:
    """Slabs per column of the plane view of ``n_words`` words: a multiple
    of :data:`MAX_PLANE_BLOCK`, or, below that many, a power of two."""
    p = max(1, -(-int(n_words) // LANE_WORDS))
    if p >= MAX_PLANE_BLOCK:
        return -(-p // MAX_PLANE_BLOCK) * MAX_PLANE_BLOCK
    return 1 << (p - 1).bit_length()


def plane_block(m: int) -> int:
    """Slabs per grid block for ``m`` members: the largest power of two
    whose two buffers per member and per output fit the VMEM budget."""
    fit = _PLANE_VMEM_BYTES // (2 * (int(m) + 1) * LANE_WORDS * 4)
    return max(1, min(MAX_PLANE_BLOCK, 1 << (max(fit, 1).bit_length() - 1)))


def host_planes(words: np.ndarray) -> np.ndarray:
    """Host uint32[N, n_words] as the plane view uint32[N, P, 8, 128],
    zero-padded to :func:`plane_count` slabs (no copy when none is needed)."""
    words = np.asarray(words, np.uint32)
    n, nw = words.shape
    width = plane_count(nw) * LANE_WORDS
    if width != nw:
        words = np.pad(words, ((0, 0), (0, width - nw)))
    return np.ascontiguousarray(words).reshape(n, -1, *SLAB)


def _bare_circuit(m: int, t, truth, weights, kind: str):
    """The circuit of a bare threshold over ``m`` members, or the constant
    word of its answer (T <= 0 or T > m)."""
    if weights is not None:
        from repro.core.weighted import build_weighted_threshold_circuit

        if t is None or len(weights) != m:
            raise ValueError(f"{len(weights)} weights for {m} members, T={t}")
        return build_weighted_threshold_circuit(list(weights), t)
    if truth is not None:
        return _ckt.build_symmetric_circuit(m, list(truth), kind)
    if t is None:
        raise ValueError("a threshold needs T")
    if t <= 0:
        return 0xFFFFFFFF
    if t > m:
        return 0
    return _ckt.build_threshold_circuit(m, t, kind)


def _plane_kernel(slots_ref, *refs, circuit: _ckt.Circuit, m: int, bb: int):
    del slots_ref  # read by the index maps
    ins, out_ref = refs[:m], refs[m]
    zeros = jnp.zeros(SLAB, jnp.uint32)
    ones = jnp.full(SLAB, 0xFFFFFFFF, jnp.uint32)

    def slab(s, carry):
        rows = [r[s] for r in ins]
        out_ref[s] = circuit.evaluate(rows, zeros=zeros, ones=ones)[0]
        return carry

    jax.lax.fori_loop(0, bb, slab, 0)


@functools.partial(
    jax.jit, static_argnames=("t", "n_words", "truth", "weights", "kind", "interpret")
)
def threshold_pallas(
    planes: jax.Array,
    slots: jax.Array,
    t: int | None = None,
    *,
    n_words: int,
    truth: tuple | None = None,
    weights: tuple | None = None,
    kind: str = "ssum",
    interpret: bool = False,
) -> jax.Array:
    """theta(T, members) fused, reading each member's plane in place;
    ``truth`` selects an arbitrary symmetric function, ``weights`` a
    weighted threshold (binary-decomposed circuit).

    planes: the plane view uint32[N, P, 8, 128] (:func:`host_planes`);
    slots: int32[m], the members' columns, in the circuit's input order
    (repeats allowed).  Returns uint32[n_words].
    """
    m = int(slots.shape[0])
    p = int(planes.shape[1])
    circuit = _bare_circuit(m, t, truth, weights, kind)
    if isinstance(circuit, int):
        return jnp.full((n_words,), circuit, jnp.uint32)
    bb = min(plane_block(m), p)

    def member(j):
        return pl.BlockSpec((None, bb, *SLAB), lambda i, s: (s[j], i, 0, 0))

    out = pl.pallas_call(
        functools.partial(_plane_kernel, circuit=circuit, m=m, bb=bb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(p // bb,),
            in_specs=[member(j) for j in range(m)],
            out_specs=pl.BlockSpec((bb, *SLAB), lambda i, s: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((p, *SLAB), jnp.uint32),
        interpret=interpret,
    )(jnp.asarray(slots, jnp.int32), *([planes] * m))
    out = out.reshape(-1)  # a bitcast: each slab is 1024 consecutive words
    return out if out.shape[0] == n_words else out[:n_words]


def threshold_rows(
    bitmaps: jax.Array,
    t: int | None = None,
    *,
    truth: tuple | None = None,
    weights: tuple | None = None,
    kind: str = "ssum",
    interpret: bool = False,
) -> jax.Array:
    """:func:`threshold_pallas` over explicit rows uint32[m, n_words]:
    the rows are padded and reshaped into a plane view once, and read as
    members ``0..m-1``.  Returns uint32[n_words]."""
    bitmaps = jnp.asarray(bitmaps, jnp.uint32)
    m, n_words = bitmaps.shape
    width = plane_count(n_words) * LANE_WORDS
    planes = jnp.pad(bitmaps, ((0, 0), (0, width - n_words))).reshape(m, -1, *SLAB)
    return threshold_pallas(
        planes, np.arange(m, dtype=np.int32), t, n_words=n_words, truth=truth,
        weights=weights, kind=kind, interpret=interpret,
    )
