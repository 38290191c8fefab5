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
lanes * 32 bits = one packed VPU tile of bit positions).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

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


@functools.partial(
    jax.jit, static_argnames=("t", "block_words", "interpret", "kind", "truth", "weights")
)
def threshold_pallas(
    bitmaps: jax.Array,
    t: int | None = None,
    *,
    truth: tuple | None = None,
    weights: tuple | None = None,
    kind: str = "ssum",
    block_words: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """theta(T, .) fused; ``truth`` selects an arbitrary symmetric function,
    ``weights`` a weighted threshold (binary-decomposed circuit).

    bitmaps: uint32[N, n_words].  Returns uint32[n_words].
    """
    bitmaps = jnp.asarray(bitmaps, jnp.uint32)
    n, n_words = bitmaps.shape
    if weights is not None:
        from repro.core.weighted import build_weighted_threshold_circuit

        assert t is not None and len(weights) == n
        circuit = build_weighted_threshold_circuit(list(weights), t)
    elif truth is not None:
        circuit = _ckt.build_symmetric_circuit(n, list(truth), kind)
    else:
        assert t is not None
        if t <= 0:
            return jnp.full((n_words,), 0xFFFFFFFF, jnp.uint32)
        if t > n:
            return jnp.zeros((n_words,), jnp.uint32)
        circuit = _ckt.build_threshold_circuit(n, t, kind)
    return run_circuit_pallas(
        bitmaps, circuit, block_words=block_words, interpret=interpret
    )
